"""Share of the traced train steps' window (first step's dispatch to the
last device operation) in which no operation ran on the chip."""
from metrics import device_idle_frac


def read(w, ctx):
    return device_idle_frac.read(w, ctx)
