"""Share of its roofline that ``paged_flash_attention`` reached on the
traced prefill packs, in percent: the least time the packs' real chunks
and prefixes need (operations bound them at chunk lengths above a few
dozen tokens) over the kernel's device time."""
import counts
from metrics._common import (FLASH_KERNEL, device_ops, roofline,
                             traced_ticks)
import trace_reduce


def read(w, ctx):
    packs = [p for t in traced_ticks(ctx) for p in t.packs]
    evs = trace_reduce.matching(device_ops(ctx), FLASH_KERNEL)
    if not packs or not evs:
        return None
    f, b = counts.prefill_attention(w.c, packs)
    return roofline(f, b, sum(e.dur for e in evs), ctx["peaks"])
