"""Tokens trained per second: every step sent in the window, times its
tokens, over the window from its open to the end of the wait for the last
step."""
import train


def read(w, ctx):
    return w.steps * train.tokens_per_step(w.job) / w.seconds
