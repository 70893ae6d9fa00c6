"""Model operations the traced steps' real tokens need, over the steps'
summed device time times the chip's bf16 peak, in percent."""
import counts
from metrics._common import STEP_MODULE, roofline, traced_ticks


def read(w, ctx):
    tr = ctx.get("trace_data")
    if tr is None:
        return None
    ticks = [t for t in traced_ticks(ctx) if t.packs or t.lanes]
    flops = sum(counts.step_flops(w.c, t.packs, t.lanes) for t in ticks)
    secs = sum(e.dur for evs in tr.modules.values() for e in evs
               if any(n in e.name for n in STEP_MODULE))
    if not ticks or secs <= 0:
        return None
    return roofline(flops, 0.0, secs, ctx["peaks"])
