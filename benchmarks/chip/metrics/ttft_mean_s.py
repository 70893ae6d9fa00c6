"""Mean over every request due in the window of the time from its due time
to its first client-visible token (age at the close if none came)."""
from metrics._common import ttfts


def read(w, ctx):
    t = ttfts(w, first_round=True)
    return sum(t) / len(t) if t else None
