"""Device milliseconds per traced train step, forward and backward, in the
scopes ``embed``, ``head`` and ``loss``: the work over the vocabulary's
table."""
from metrics._common import scope_ms


def read(w, ctx):
    return scope_ms(ctx, "train_vocab_ms")
