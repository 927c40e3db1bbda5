"""Device time of the jitted AIPO train step per call, from the profiler
trace (the program ``jit_train_step`` on the trainer's chips)."""


def is_train_step(name):
    return name.startswith("jit_train_step")


def read(ctx):
    if ctx.trace is None or not ctx.trainer_devices:
        return None
    per = []
    for d in ctx.trainer_devices:
        s, n = ctx.trace.module_seconds(is_train_step, d)
        if n:
            per.append(s / n)
    return 1e3 * sum(per) / len(per) if per else None
