"""Mean host time of ``Engine.tick`` outside the ``run_batch`` inside it
(admission, policy, batch formation, bookkeeping), over the window's ticks
that dispatched work, in milliseconds."""


def read(w, ctx):
    ts = [(t.t1 - t.t0) - (t.rb1 - t.rb0) for t in w.ticks]
    return 1e3 * sum(ts) / len(ts) if ts else None
