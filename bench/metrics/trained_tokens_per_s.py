"""Positions (prompt + generated) of every row trained in the window's
whole steps, over the window's time."""


def read(ctx):
    r = ctx.run
    return sum(c["positions_trained"] for c in r.counts) / r.window_s
