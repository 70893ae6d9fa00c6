"""Share of the traced window in which no operation ran on the chip:
1 - (union of device op intervals) / (first traced tick's start to the
last one's end, on the trace clock)."""


def read(w, ctx):
    d = ctx.get("device_time")
    if not d or d["window_s"] <= 0:
        return None
    return 1.0 - d["busy_s"] / d["window_s"]
