"""Operations the traced train steps need (``counts.train_step_flops``:
forward and backward, recomputation not counted) over the step programs'
summed device time times the chip's bf16 peak, in percent."""
import counts
from metrics._common import roofline


def read(w, ctx):
    mods = ctx.get("step_modules")
    if not mods or mods["count"] == 0:
        return None
    flops = mods["count"] * counts.train_step_flops(
        w.c, w.job["batch"], w.job["seq_len"])
    return roofline(flops, 0.0, mods["seconds"], ctx["peaks"])
