"""Share of its roofline that ``paged_attention_fused`` reached on the
traced decode lanes, in percent: the least time the lanes' real context
lengths need (bytes bound them: Hq/Hkv operations per byte against a
ridge of 240) over the kernel's device time."""
import counts
from metrics._common import (DECODE_KERNEL, device_ops, roofline,
                             traced_ticks)
import trace_reduce


def read(w, ctx):
    lanes = [n for t in traced_ticks(ctx) for n in t.lanes]
    evs = trace_reduce.matching(device_ops(ctx), DECODE_KERNEL)
    if not lanes or not evs:
        return None
    f, b = counts.decode_attention(w.c, lanes)
    return roofline(f, b, sum(e.dur for e in evs), ctx["peaks"])
