"""The paged decode-attention kernel against its roofline: the attention
the window's trained rows required over their decode (each token reads
its row's cached keys and values once per layer) over the kernel's
device time per generator chip."""
from bench import kernels, work


def read(ctx):
    if ctx.trace is None or ctx.peak is None or not ctx.generator_devices:
        return None
    secs = ctx.trace.op_seconds(kernels.matcher("paged_attention"),
                                ctx.generator_devices)
    t = sum(secs.values()) / len(secs)
    if t <= 0:
        return None
    a, cfg = ctx.run.args, ctx.spec
    rows = sum(c["rows"] for c in ctx.run.counts) * cfg["n_layers"]
    return work.roofline_pct(
        "paged_attention", t, ctx.peak, rows=rows,
        prompt_len=a.prompt_len, max_new=a.max_new, heads=cfg["n_heads"],
        kv_heads=cfg["n_kv_heads"], head_dim=cfg["head_dim"])
