#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload sc2-3b.decode-long --seed 7 \
        --seconds 30 --trace 0

The cell, its configuration, traffic mix, limits and metrics are found by
name from ``BENCHMARK.json``.  Set-up (process start to the window's
start) builds the async RL loop through the launcher, makes the weights on
the device from ``--seed`` and warms up every shape; the window times
whole train steps for ``--seconds``; then the program's state is freed and
the plain reference checks what the first steps produced.  With
``--trace 1`` the window is traced and the per-layer metrics are printed
instead of the end-to-end ones.

The last line of standard output is one JSON object; the numbers compared
with their limits are also the last lines of standard error.  No
accelerator, or fewer chips than the cell asks for: exit code 1 and no
result line.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from types import SimpleNamespace  # noqa: E402

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [REPO_ROOT, os.path.join(REPO_ROOT, "src")]


def enable_cache():
    """JAX's persistent compilation cache at a fixed directory in the
    checkout (or where ``JAX_COMPILATION_CACHE_DIR`` points), every
    program cached however fast it compiled."""
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(REPO_ROOT, ".jax_cache"))
    import jax
    from repro.launch import train
    train.enable_compile_cache()
    jax.config.update("jax_compilation_cache_dir",
                      os.environ["JAX_COMPILATION_CACHE_DIR"])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def metric_context(cell, out, reduced):
    from bench import peaks, trace_reduce
    kind = out.devices[0].device_kind
    ctx = SimpleNamespace(run=out, cell=cell, spec=cell.config,
                          trace=reduced, peak=None, trainer_devices=None,
                          generator_devices=None, device_keys=None)
    if out.devices[0].platform == "tpu":
        ctx.peak = peaks.peak(kind)
    if reduced is not None:
        ids = [d.id for d in out.devices]
        ctx.device_keys = trace_reduce.for_devices(reduced, ids)
        if out.meshes is None:
            ctx.trainer_devices = ctx.generator_devices = ctx.device_keys
        else:
            t, g = out.meshes
            ctx.trainer_devices = trace_reduce.for_devices(
                reduced, [d.id for d in t.devices.flat])
            ctx.generator_devices = trace_reduce.for_devices(
                reduced, [d.id for d in g.devices.flat])
    return ctx


def compute_metrics(cell, ctx, trace):
    from bench import spec as bspec
    metrics = {}
    for m in cell.metrics(trace):
        value = bspec.metric_reader(m["name"], cell.root)(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return metrics


def result_line(cell, out, metrics, compared, correct, ctx, trace):
    import jax
    dev = out.devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": out.memory_peak_bytes}
    res = {"correct": bool(correct), "attempted": len(out.steps),
           "failed": 0, "metrics": metrics, "device": device}
    keys = ctx.device_keys
    if trace and keys:
        device["busy_s"] = sum(ctx.trace.busy_s(k) for k in keys) / len(keys)
        device["window_s"] = out.window_s
        res["breakdown"] = {"device_ops": ctx.trace.top_ops(10, keys),
                            "idle_gaps": ctx.trace.idle_gaps(10, keys)}
    res["compared"] = compared
    return res


def work_summary(out):
    keys = ("rows_ended_by_end_id", "rows_ended_by_budget", "pad_draws",
            "positions_decoded", "positions_trained")
    tot = {k: sum(c[k] for c in out.counts) for k in keys}
    tot["steps_in_window"] = len(out.steps)
    tot["positions_trained_per_step"] = sorted(
        {c["positions_trained"] for c in out.counts})
    b = out.probe.boundaries[out.probe.open_i:out.probe.close_i + 1]
    tot["step_ms"] = [round(1e3 * (y - x), 1) for x, y in zip(b, b[1:])]
    return tot


def run_once(cell, seed, seconds, trace, *, t_start, require_chip=True,
             plant=None):
    """One run of ``cell``: the result line's object, after printing the
    work counts, the compile count and the compared numbers."""
    import shutil
    from bench import compare, harness, trace_reduce

    out = harness.run_cell(cell, seed, seconds, trace, t_start=t_start,
                           require_chip=require_chip, plant=plant)
    print("work: " + json.dumps(work_summary(out)), flush=True)
    print(f"compiles in window: {out.compiles_in_window}", flush=True)
    print(f"device bytes live after the program was freed: "
          f"{out.live_bytes_after_release}", flush=True)
    values = compare.readings(out.program, out.reference,
                              cell.limits["logp_tail_nats"])
    correct, compared = compare.judge(values, cell.limits)
    print("panel: " + json.dumps(compare.panel(out.program, out.reference)),
          flush=True)
    reduced = None
    if trace:
        reduced = trace_reduce.load(out.trace_dir)
        shutil.rmtree(out.trace_dir, ignore_errors=True)
    ctx = metric_context(cell, out, reduced)
    metrics = compute_metrics(cell, ctx, trace)
    res = result_line(cell, out, metrics, compared, correct, ctx, trace)
    for name in compare.NAMES:
        if values[name] is None:
            print(f"{name} not defined: the reference keeps no leaf",
                  file=sys.stderr)
        elif name not in compared:
            print(f"{name} {values[name]!r} not compared", file=sys.stderr)
    for name, v in compared.items():
        print(f"{name} {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    return res


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0, choices=[0, 1])
    opts = ap.parse_args(argv)

    from bench import spec as bspec
    cell = bspec.load_cell(opts.workload)
    enable_cache()
    res = run_once(cell, opts.seed, opts.seconds, bool(opts.trace),
                   t_start=T_START)
    print(json.dumps(res), flush=True)


if __name__ == "__main__":
    main()
