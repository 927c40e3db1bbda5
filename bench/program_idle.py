#!/usr/bin/env python3
"""Run one benchmark cell traced with the program's own tracer on, and
charge the device's idle time to the program's spans.

    python3 bench/program_idle.py --workload sc2-3b.decode-long --seed 7 \
        --seconds 30

The run is ``bench/run.py --trace 1``'s: the same set-up, window, trace,
reference check and per-layer metrics.  Besides, ``repro.obs`` is enabled
before the loop is built, so the profiler trace also holds the program's
``repro:*`` spans, and the program's registry is read as the window opens
and closes.  Before the result line it prints ``program idle: {...}``
(``bench/program_spans.py``: idle seconds charged per span, the shares
under only ``-wait`` spans and under none, the clock shift), and the
result line's ``metrics`` also hold the numbers of
``program_spans.NUMBERS``.  The tracer's own cost is the difference of
the per-layer metrics from a ``bench/run.py --trace 1`` run of the same
seed.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import run as brun  # noqa: E402  (puts src/ on the path too)


@contextlib.contextmanager
def registry_at_window_edges(harness):
    """Let ``harness.run_cell`` build a probe that also keeps the
    program's registry as the window opens (``registry_open``) and as it
    closes (``registry_close``), each read at the top of that step's
    boundary."""
    from repro.obs import metrics as obs_metrics
    base = harness.Probe

    class Probe(base):
        registry_open = registry_close = None

        def boundary(self):
            snap = obs_metrics.registry().snapshot()
            opening = self.open_i is None
            try:
                base.boundary(self)
            except harness.WindowClosed:
                self.registry_close = snap
                raise
            if opening and self.open_i is not None:
                self.registry_open = snap

    harness.Probe = Probe
    try:
        yield
    finally:
        harness.Probe = base


def run_traced(cell, seed, seconds, *, t_start, require_chip=True):
    """One traced run of ``cell`` with the tracer on: the result line's
    object, after printing the work counts and ``program idle``."""
    from bench import compare, harness, program_spans, trace_reduce
    from repro.obs import trace as obs_trace

    obs_trace.enable("bench")
    try:
        with registry_at_window_edges(harness):
            out = harness.run_cell(cell, seed, seconds, True,
                                   t_start=t_start,
                                   require_chip=require_chip)
    finally:
        obs_trace.disable()
    print("work: " + json.dumps(brun.work_summary(out)), flush=True)
    values = compare.readings(out.program, out.reference,
                              cell.limits["logp_tail_nats"])
    correct, compared = compare.judge(values, cell.limits)
    reduced = trace_reduce.load(out.trace_dir)
    program = program_spans.load(out.trace_dir, reduced)
    shutil.rmtree(out.trace_dir, ignore_errors=True)
    ctx = brun.metric_context(cell, out, reduced)
    ctx.program = program
    if ctx.device_keys:
        print("program idle: " + json.dumps(
            program.summary(ctx.device_keys)), flush=True)
    metrics = brun.compute_metrics(cell, ctx, True)
    for name, (unit, read) in program_spans.NUMBERS.items():
        value = read(ctx)
        if value is not None:
            metrics[name] = {"value": float(value), "unit": unit}
    return brun.result_line(cell, out, metrics, compared, correct, ctx,
                            True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    opts = ap.parse_args(argv)

    from bench import spec as bspec
    cell = bspec.load_cell(opts.workload)
    brun.enable_cache()
    res = run_traced(cell, opts.seed, opts.seconds, t_start=T_START)
    print(json.dumps(res), flush=True)


if __name__ == "__main__":
    main()
