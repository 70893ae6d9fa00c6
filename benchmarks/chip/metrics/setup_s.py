"""Seconds from process start to the window's open: TPU start-up, weights,
the program's own start-up, step shapes compiled or loaded, traffic
warm-in."""


def read(w, ctx):
    return ctx["setup_s"]
