"""Host wall time per fused step call (packing, upload, dispatch, device
time, readback), from the backend's ``dispatch_stats`` over the window,
in milliseconds."""


def read(w, ctx):
    n = w.dispatch1["mixed_calls"] - w.dispatch0["mixed_calls"]
    s = w.dispatch1["mixed_s"] - w.dispatch0["mixed_s"]
    return 1e3 * s / n if n else None
