"""Host time per train step spent making its batch and sending it (the
jitted call returns before the device finishes), over the window, in
milliseconds."""


def read(w, ctx):
    return 1e3 * w.dispatch_s / w.steps if w.steps else None
