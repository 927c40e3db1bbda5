"""Time per train step the consumer waited in the weight fabric's
publish (the controller's ``publish_wait_s``, differenced over the
window), in ms."""


def read(ctx):
    p = ctx.run.probe
    d = p.stats_close["publish_wait_s"] - p.stats_open["publish_wait_s"]
    return 1e3 * d / len(ctx.run.steps)
