"""Share of the window in which no operation ran on the device, from the
profiler trace, averaged over the cell's chips."""


def read(ctx):
    if ctx.trace is None or not ctx.device_keys:
        return None
    busy = [ctx.trace.busy_s(k) for k in ctx.device_keys]
    return 100.0 * (1.0 - sum(busy) / len(busy) / ctx.run.window_s)
