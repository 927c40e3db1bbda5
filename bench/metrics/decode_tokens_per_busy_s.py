"""Tokens the generator decoded for the rows trained in the window, over
the generator's busy time (the controller's ``gen_busy_s``, differenced
over the window)."""


def read(ctx):
    p = ctx.run.probe
    busy = p.stats_close["gen_busy_s"] - p.stats_open["gen_busy_s"]
    if busy <= 0:
        return None
    return sum(c["positions_decoded"] for c in ctx.run.counts) / busy
