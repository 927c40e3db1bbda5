"""Process start to the window's start: imports, building the loop,
making the weights, compiling or loading every program, warm-up steps."""


def read(ctx):
    return ctx.run.setup_s
