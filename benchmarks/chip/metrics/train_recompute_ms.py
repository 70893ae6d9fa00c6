"""Device milliseconds per traced train step that the backward pass spends
recomputing forward work inside the layers' ``jax.checkpoint`` regions
(ops under ``rematted_computation``), every scope."""
from metrics._common import split_ms


def read(w, ctx):
    return split_ms(ctx, phase="recompute")
