"""Share of the window the trainer's consumer spent waiting for the next
batch (the controller's ``train_idle_s``, differenced over the window)."""


def read(ctx):
    p = ctx.run.probe
    d = p.stats_close["train_idle_s"] - p.stats_open["train_idle_s"]
    return 100.0 * d / ctx.run.window_s
