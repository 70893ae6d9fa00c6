"""Device milliseconds per traced train step, forward and backward, in the
scope ``attn``: attention, from its norm to its residual add."""
from metrics._common import scope_ms


def read(w, ctx):
    return scope_ms(ctx, "train_attn_ms")
