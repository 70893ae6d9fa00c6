"""Median gap between consecutive client-visible tokens of one round,
over every gap that ends in the window, in milliseconds: a tick that
carries decode lanes only."""
from metrics._common import gaps_ms, percentile


def read(w, ctx):
    return percentile(gaps_ms(w), 50)
