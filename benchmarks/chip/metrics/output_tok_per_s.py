"""Client-visible tokens emitted in the window, over the window."""


def read(w, ctx):
    n = sum(1 for rec in w.recs for r in rec.rounds for t in r.stamps
            if w.t_open <= t < w.t_close)
    return n / w.seconds
