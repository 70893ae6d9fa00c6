"""Mean gap between consecutive client-visible tokens of one round, over
every gap that ends in the window, in milliseconds: the time per token a
client's stream sees."""
from metrics._common import gaps_ms


def read(w, ctx):
    g = gaps_ms(w)
    return sum(g) / len(g) if g else None
