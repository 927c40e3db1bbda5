"""The trainer's flash-attention forward kernel against its roofline:
the causal attention its calls require for the window's train steps (one
call per layer per step) over the kernel's device time per chip."""
from bench import kernels, work


def read(ctx):
    if ctx.trace is None or ctx.peak is None or not ctx.trainer_devices:
        return None
    secs = ctx.trace.op_seconds(kernels.matcher("flash_attention"),
                                ctx.trainer_devices)
    t = sum(secs.values()) / len(secs)
    if t <= 0:
        return None
    a, cfg = ctx.run.args, ctx.spec
    calls = len(ctx.run.steps) * cfg["n_layers"]
    return work.roofline_pct(
        "flash_attention", t / calls, ctx.peak,
        rows=a.n_prompts * a.n_per_prompt, seq=a.prompt_len + a.max_new,
        heads=cfg["n_heads"], kv_heads=cfg["n_kv_heads"],
        head_dim=cfg["head_dim"])
