"""Device milliseconds per traced train step in the scope ``adamw``: the
gradient's global norm and the AdamW update."""
from metrics._common import scope_ms


def read(w, ctx):
    return scope_ms(ctx, "train_adamw_ms")
