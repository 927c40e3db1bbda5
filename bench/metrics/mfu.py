"""Model FLOPs of every program the loop requires for the rows trained in
the window (their prefill, their decode steps, the trainer's forward and
backward; nothing recomputed, no padding or idle slots), over the
window's time, the cell's chips and the chip's bf16 peak, in percent."""
from bench import work


def read(ctx):
    if ctx.peak is None:
        return None
    a = ctx.run.args
    rows = sum(c["rows"] for c in ctx.run.counts)
    flops = rows * work.rl_row_flops(ctx.spec, prompt_len=a.prompt_len,
                                     max_new=a.max_new)
    chips = len(ctx.run.devices)
    return 100.0 * flops / (ctx.run.window_s * chips
                            * ctx.peak["flops_per_s"])
