"""The expert layer's grouped products (``gmm``, ``tgmm``, and the
operations that stage each call's weights, found by producer in
``bench/expert_matmul.py``) against their roofline: the
work the window's expert-layer passes required over that device time,
in percent.

The trace gives the window's calls and time.  A trainer pass makes three
``tgmm`` calls and six ``gmm`` calls (nine where the layers are
recomputed in the backward, ``remat_layers``), a generator pass (decode
step or prefill) three ``gmm`` calls, which splits the window's passes by
side; the recomputation is work done, not required, and is not counted.
The work of a pass is the mean of its side over the run, from the
program's counters: ``moe.pairs_held``, ``moe.experts_hit`` and
``moe.products`` count every pass, their ``.trainer`` parts the
trainer's (``bench/expert_matmul.py`` turns pairs and experts hit into
FLOPs and bytes).  None where the program keeps no such counters."""
from bench import expert_matmul as em


def read(ctx):
    if ctx.trace is None or ctx.peak is None or not ctx.generator_devices:
        return None
    from repro.obs import metrics as obs_metrics
    snap = obs_metrics.registry().snapshot()
    total = {n: snap.get(n, {}).get("value", 0.0)
             for n in ("moe.products", "moe.pairs_held", "moe.experts_hit")}
    train = {n: snap.get(n + ".trainer", {}).get("value", 0.0)
             for n in total}
    if total["moe.products"] <= 0:
        return None
    dev = ctx.generator_devices
    cfg = ctx.spec
    names = set().union(*(ctx.trace.ops[d] for d in dev))
    secs = ctx.trace.op_seconds(em.product_matcher(names), dev)
    t = sum(secs.values()) / len(secs)
    if t <= 0:
        return None
    gmm = sum(ctx.trace.op_calls_matching(em.matcher("gmm"), d)
              for d in dev) / len(dev)
    tgmm = sum(ctx.trace.op_calls_matching(em.matcher("tgmm"), d)
               for d in dev) / len(dev)
    per_pass = 3 if ctx.run.cfg.remat_layers else 2   # gmm per tgmm
    passes = {"trainer": tgmm / 3, "generator": (gmm - per_pass * tgmm) / 3}
    counts = {"trainer": train,
              "generator": {n: total[n] - train[n] for n in total}}
    least = 0.0
    for side, n_passes in passes.items():
        c = counts[side]
        if n_passes <= 0 or c["moe.products"] <= 0:
            continue
        least += n_passes * em.least_seconds(
            ctx.peak, pairs=c["moe.pairs_held"] / c["moe.products"],
            hits=c["moe.experts_hit"] / c["moe.products"],
            d_model=cfg["d_model"], d_expert=cfg["d_ff"],
            passes=3 if side == "trainer" else 1)
    return em.roofline_pct(least, t)
