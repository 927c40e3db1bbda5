#!/usr/bin/env python3
"""Readings that set a cell's limits: for each seed, one run of the
program at the cell's own size with the reference check, then, on the
same rows, the control and the planted faults in the program's place.

    python3 bench/control.py --workload sc2-3b.decode-long \
        --seeds 21 22 23 --seconds 5

* ``program``: the numbers of the run itself (a sound run);
* ``control``: the reference in bfloat16 -- the nearest precision below
  the configuration's float32 -- following the same steps;
* ``half_batch``: the reference training on half of each batch, the mean
  taken over the rest;
* ``token_altered``: one sampled token per row replaced after its
  behaviour log-prob was taken;
* a step that returns its state unchanged, and an exchange that never
  reaches the generator, read 1 on ``change_gap`` by that number's
  measure (where the reference moves any leaf) and need no run.

Each seed prints one ``readings`` JSON line, with the verdict of
``compare.judge`` under the cell's limits for the run and each fault, and
``panel``: the statistics the limits were chosen from.  Not part of a
benchmark run; its readings and the limits set from them are in PERF.md.
"""
import argparse
import json
import os
import sys
import time

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [REPO_ROOT, os.path.join(REPO_ROOT, "src")]


def altered(tokens, prompt_len):
    toks = tokens.copy()
    col = prompt_len + 1
    toks[:, col] = (toks[:, col] == 5) + 5
    return toks, col


def readings_for_seed(cell, seed, seconds, *, require_chip=True):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from bench import compare, harness, replay, spec as bspec, \
        weights as bw

    out = harness.run_cell(cell, seed, seconds, False,
                           t_start=time.perf_counter(),
                           require_chip=require_chip)
    a, ref, prog = out.args, out.reference, out.program
    nats = cell.limits["logp_tail_nats"]
    rec = {"seed": seed, "program": compare.readings(prog, ref, nats)}
    refmod = bspec.reference_module(cell.config["reference"], cell.root)
    kw = dict(lr=a.lr, rho=a.rho, n_per_prompt=a.n_per_prompt,
              prompt_len=a.prompt_len,
              rows_per_block=int(cell.traffic["rows_per_block"]))
    with jax.default_device(out.devices[0]):
        ctrl = replay.follow(refmod, cell.config, seed, out.batches,
                             dtype=jnp.bfloat16, **kw)
        first = dict(out.batches[0],
                     behavior_logp=np.pad(ctrl["logp"], ((0, 0), (1, 0))))
        as_prog = {"batches": [first] + out.batches[1:],
                   "losses": ctrl["losses"], "mean_logp": ctrl["mean_logp"],
                   "grad_norm": ctrl["grad_norm"],
                   "moments": ctrl["grad_norms"],
                   "change": ctrl["change"]}
        rec["control"] = compare.readings(as_prog, ref, nats)
        rec["panel"] = {"program": compare.panel(prog, ref),
                        "control": compare.panel(as_prog, ref)}
        for k, p in (("program", prog), ("control", as_prog)):
            rec["panel"][k]["gaps"] = [round(float(g), 5) for g in
                                       compare.action_gaps(p["batches"][0],
                                                           ref["logp"])]
        half = replay.follow(refmod, cell.config, seed,
                             [{k: v[:len(v) // 2] for k, v in b.items()}
                              for b in out.batches], **kw)
        as_half = dict(prog, losses=half["losses"],
                       mean_logp=half["mean_logp"],
                       grad_norm=half["grad_norm"],
                       moments=half["grad_norms"], change=half["change"])
        rec["half_batch"] = compare.readings(as_half, ref, nats)
        rec["panel"]["half_batch"] = compare.panel(as_half, ref)
        params, _ = bw.make(refmod, cell.config, seed, a.prompt_len)
        toks, col = altered(out.batches[0]["tokens"], a.prompt_len)
        lp = replay.batch_logp(refmod, cell.config, params, toks,
                               dtype=jnp.float32,
                               rows_per_block=kw["rows_per_block"])
        batches = [dict(out.batches[0], tokens=toks)] + out.batches[1:]
        rec["token_altered"] = compare.readings(
            dict(prog, batches=batches),
            dict(ref, logp=lp), nats)
    # a step that returns its state unchanged, or an exchange that never
    # reaches the generator, leaves the weights at version 3 as they were
    rec["state_unchanged"] = rec["exchange_left_out"] = dict(
        rec["program"], change_gap=compare.worst_leaf(
            np.zeros_like(ref["change"]), ref["change"],
            compare.kept_for_change(ref)))
    rec["correct"] = {k: compare.judge(rec[k], cell.limits)[0] for k in
                      ("program", "control", "half_batch", "token_altered",
                       "state_unchanged")}
    return rec


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    opts = ap.parse_args()
    from bench import run as brun, spec as bspec
    cell = bspec.load_cell(opts.workload)
    brun.enable_cache()
    for seed in opts.seeds:
        rec = readings_for_seed(cell, seed, opts.seconds)
        print("readings " + json.dumps(rec), flush=True)


if __name__ == "__main__":
    main()
