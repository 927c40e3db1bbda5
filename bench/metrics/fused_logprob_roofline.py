"""The trainer's fused log-prob kernel (forward and backward) against its
roofline: the bytes its calls require for the window's train steps over
the kernel's device time per chip, in percent."""
from bench import kernels, work


def read(ctx):
    if ctx.trace is None or ctx.peak is None or not ctx.trainer_devices:
        return None
    secs = ctx.trace.op_seconds(kernels.matcher("fused_logprob"),
                                ctx.trainer_devices)
    t = sum(secs.values()) / len(secs)
    if t <= 0:
        return None
    a, cfg = ctx.run.args, ctx.spec
    steps = len(ctx.run.steps)
    return work.roofline_pct("fused_logprob", t / steps, ctx.peak,
                             rows=a.n_prompts * a.n_per_prompt,
                             seq=a.prompt_len + a.max_new, vocab=cfg["vocab"])
