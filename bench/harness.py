"""One run of one cell: build the async RL loop the way users run it,
warm it up, time a window of whole train steps, optionally trace it,
then check what it produced against the plain reference.

The window drives ``launch.train.build_controller(cfg, args, meshes)
.run()`` with ``args`` parsed by the launcher's own parser from the cell's
traffic file.  Everything the benchmark observes it observes from
outside: thin wrappers on the executors' own methods record each step's
rows, the step boundaries, and host spans for the trace.  The window
opens at the boundary after ``warmup_steps`` steps and closes at the
first boundary ``--seconds`` later that ends an even number of steps:
with staleness 1 the loop keeps two batches in flight and its steps come
in pairs, one long and one short, so a window of an odd count would add
half a pair's difference to the rate.  The loop is then stopped by
raising from the trainer's metrics read, which the controller's error
path unwinds.
"""
from __future__ import annotations

import contextlib
import gc
import os
import shutil
import time
from types import SimpleNamespace
from typing import Dict, List, Optional

import jax
import numpy as np

from bench import replay, spec as bspec, weights as bw

PAD, EOS = 0, 2


class WindowClosed(Exception):
    """Raised on the controller's consumer thread to end the loop."""


class WorkMismatch(RuntimeError):
    """A train step's rows differ from the work the cell declares."""


class CompileCounter:
    """Counts programs compiled or loaded from the persistent cache."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in self.EVENTS:
            self.n += 1

    def close(self):
        jax.monitoring.unregister_event_duration_listener(self._on)


def program_config(cell):
    """The program's config for the cell: the registry entry with the
    configuration file's reduced keys applied, checked against every
    size the file states."""
    from repro import configs
    c = cell.config
    cfg = configs.get_config(c["arch"]).replace(
        **{k: c[k] for k in c["reduced"]})
    for k in ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
              "d_ff", "vocab", "act", "norm", "bias", "rope_theta",
              "window", "tie_embeddings"):
        have = getattr(cfg, k)
        assert have == c[k], f"{c['name']}: program {k}={have!r}, file {c[k]!r}"
    return cfg


def batch_counts(tokens, mask, prompt_len, max_new):
    """Work in one trained batch: rows ended by the end id, pad draws,
    positions decoded and trained."""
    tokens = np.asarray(tokens)
    gen = tokens[:, prompt_len:prompt_len + max_new]
    has_eos = (gen == EOS).any(axis=1)
    first = np.where(has_eos, (gen == EOS).argmax(axis=1), max_new)
    before = np.arange(gen.shape[1])[None, :] < first[:, None]
    return {"rows": int(tokens.shape[0]),
            "rows_ended_by_end_id": int(has_eos.sum()),
            "rows_ended_by_budget": int((~has_eos).sum()),
            "pad_draws": int(((gen == PAD) & before).sum()),
            "positions_decoded": int(np.minimum(first + 1, max_new).sum()),
            "positions_trained": int(tokens.shape[0] * prompt_len
                                     + np.asarray(mask).sum())}


class Probe:
    """What the benchmark records of one run, from the executors' own
    method calls."""

    def __init__(self, cell, args, seed, seconds, *, trace_dir, ref,
                 ref_spec, probe_dir):
        self.args = args
        self.seed = seed
        self.seconds = seconds
        self.warmup = int(cell.traffic["warmup_steps"])
        self.trace_dir = trace_dir
        self.ref, self.ref_spec, self.probe_dir = ref, ref_spec, probe_dir
        self.rows = args.n_prompts * args.n_per_prompt
        self.declared = self.rows * (args.prompt_len + args.max_new)
        self.boundaries: List[float] = []
        self.step_counts: Dict[int, dict] = {}
        self.batches: Dict[int, dict] = {}
        self.losses: Dict[int, float] = {}
        self.mean_logp: Dict[int, float] = {}
        self.grad_norm: Dict[int, float] = {}
        self.moments: Dict[int, np.ndarray] = {}
        self.change: Optional[np.ndarray] = None
        self.open_i: Optional[int] = None
        self.close_i: Optional[int] = None
        self.t_open = self.t_close = None
        self.stats_open = self.stats_close = None
        self.compiles = CompileCounter()
        self.compiles_open = self.compiles_close = 0
        self.ctl = None
        self.trainer = None

    # --- trainer side ---------------------------------------------------
    def before_step(self, n, scored):
        tokens = np.asarray(scored["tokens"])
        mask = np.asarray(scored["mask"])
        c = batch_counts(tokens, mask, self.args.prompt_len,
                         self.args.max_new)
        self.step_counts[n] = c
        if c["positions_trained"] != self.declared or c["rows"] != self.rows:
            raise WorkMismatch(
                f"step {n} trained {c['positions_trained']} positions in "
                f"{c['rows']} rows; the cell declares {self.declared} in "
                f"{self.rows} ({c})")
        if n < 3:
            self.batches[n] = {
                "tokens": tokens, "mask": mask.astype(np.float32),
                "behavior_logp": np.asarray(scored["behavior_logp"],
                                            np.float32)}

    def after_step(self, n, metrics, state):
        if n < 3:
            self.losses[n] = float(metrics["loss"])
            self.mean_logp[n] = float(metrics["mean_logp"])
            self.grad_norm[n] = float(metrics["grad_norm"])
            # after the first step with a gradient Adam's first moment is
            # (1 - b1) * g: every earlier step's gradient was zero
            self.moments[n] = np.asarray(bw.leaf_norms(state.opt.m),
                                         np.float64) / (1.0 - replay.ADAM_B1)

    def boundary(self):
        """Called as the controller records step ``len(boundaries)``:
        that step's update is done (its metrics were read)."""
        t = time.perf_counter()
        self.boundaries.append(t)
        i = len(self.boundaries) - 1
        if self.open_i is None and i + 1 == self.warmup:
            self.open_i, self.t_open = i, t
            self.stats_open = dict(self.ctl.stats)
            self.compiles_open = self.compiles.n
            if self.trace_dir:
                jax.profiler.start_trace(self.trace_dir)
        elif (self.open_i is not None and t - self.t_open >= self.seconds
              and (i - self.open_i) % 2 == 0):
            jax.block_until_ready(self.trainer.state.params)
            self.t_close = time.perf_counter()
            self.close_i = i
            self.stats_close = dict(self.ctl.stats)
            self.compiles_close = self.compiles.n
            if self.trace_dir:
                jax.profiler.stop_trace()
            raise WindowClosed()

    # --- generator side ------------------------------------------------
    def on_weights(self, version, params):
        if version == 3 and self.change is None:
            self.change = bw.change_norms(self.ref, self.ref_spec, self.seed,
                                          params, self.probe_dir)

    # --- the window ----------------------------------------------------
    def window_steps(self):
        return list(range(self.open_i + 1, self.close_i + 1))


def _annotate(name):
    return jax.profiler.TraceAnnotation(f"bench:{name}")


def _wrap(obj, method, *, after=None, span=None):
    """Replace ``obj.method`` on the instance with a wrapper that records
    after the original (inside a host span when ``span`` is set)."""
    orig = getattr(obj, method)

    def wrapped(*a, **kw):
        with (_annotate(span) if span else contextlib.nullcontext()):
            out = orig(*a, **kw)
        if after is not None:
            after(out, *a, **kw)
        return out
    setattr(obj, method, wrapped)


def instrument(ctl, probe, trace: bool):
    trn = ctl.trainer.transport.executor
    probe.ctl, probe.trainer = ctl, trn

    orig_step = trn.step

    def step():
        n = trn.curr_step
        probe.before_step(n, trn.get_input("completions_with_reward"))
        with (_annotate("trainer.step") if trace
              else contextlib.nullcontext()):
            metrics = orig_step()
        probe.after_step(n, metrics, trn.state)
        return metrics
    trn.step = step

    orig_last = trn.last_metrics

    def last_metrics():
        probe.boundary()
        return orig_last()
    trn.last_metrics = last_metrics

    for gh in ctl.generators:
        gen = gh.transport.executor
        _wrap(gen, "set_weights",
              after=lambda out, params, version=None, g=gen:
              probe.on_weights(version, g.params),
              span="generator.set_weights" if trace else None)
        if trace:
            for m in ("engine_round", "engine_enqueue", "step_snapshot",
                      "begin_batch", "advance_chunk", "emit_batch"):
                _wrap(gen, m, span=f"generator.{m}")
    if trace:
        for h in ctl.executors.values():
            if h.role in ("reward", "reference"):
                _wrap(h.transport.executor, "step", span=f"{h.role}.step")
        _wrap(ctl._fabric, "publish", span="weight_plane.publish")


def inject_weights(ctl, ref, ref_spec, seed, prompt_len):
    """Initialize the trainer as the program does, then swap in the
    benchmark's weights (same tree, same placement) before the controller
    publishes version 0."""
    trn = ctl.trainer.transport.executor
    trn.init()
    shardings = jax.tree.map(lambda a: a.sharding, trn.state.params)
    params, probe_dir = bw.make(ref, ref_spec, seed, prompt_len, shardings)
    trn.state = trn.state._replace(params=params)
    trn.set_output("policy_model", params)
    trn.init = lambda: None         # the controller's init must not redraw
    return tuple(np.asarray(x) for x in probe_dir)


def _devices_used(meshes):
    if meshes is None:
        return [jax.devices()[0]]
    return [d for m in meshes for d in m.devices.flat]


def peak_bytes(devices):
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in
             devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def run_cell(cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_chip: bool = True,
             check: bool = True, plant=None):
    """Run one cell once.  Returns a namespace with everything the result
    line and the metric readers need.  ``plant(ctl)``, given by the fault
    tests only, breaks the built loop before the benchmark instruments
    it."""
    from repro.launch import train
    from repro.launch.mesh import trainer_generator_submeshes

    devs = jax.devices()
    if require_chip:
        if devs[0].platform != "tpu":
            raise SystemExit(f"no accelerator: JAX found {devs[0].platform!r}")
        if len(devs) < cell.chips:
            raise SystemExit(f"cell {cell.name} needs {cell.chips} chips, "
                             f"JAX found {len(devs)}")
    ref = bspec.reference_module(cell.config["reference"], cell.root)
    cfg = program_config(cell)
    argv = ["--arch", cell.config["arch"], "--seed",
            str(bw.program_seed(seed)), "--steps", "1000000"] \
        + list(cell.traffic["argv"])
    args = train.parse_args(argv)
    meshes = None
    if cell.traffic["placement"] == "split":
        meshes = trainer_generator_submeshes(float(cell.traffic["theta"]))
    used = _devices_used(meshes)

    trace_dir = None
    if trace:
        trace_dir = os.path.join(os.environ.get("TMPDIR", "/tmp"),
                                 f"bench_trace_{os.getpid()}")
        shutil.rmtree(trace_dir, ignore_errors=True)

    ctl = train.build_controller(cfg, args, meshes)
    probe_dir = inject_weights(ctl, ref, cell.config, seed, args.prompt_len)
    probe = Probe(cell, args, seed, seconds, trace_dir=trace_dir, ref=ref,
                  ref_spec=cell.config, probe_dir=probe_dir)
    if plant is not None:
        plant(ctl)
    instrument(ctl, probe, trace)
    try:
        ctl.run()
    except WindowClosed:
        pass
    finally:
        ctl.shutdown()
        probe.compiles.close()
    if probe.close_i is None:
        raise RuntimeError("the loop ended before the window closed")

    out = SimpleNamespace(cell=cell, args=args, seed=seed, probe=probe,
                          devices=used, all_devices=devs, cfg=cfg,
                          meshes=meshes, trace_dir=trace_dir)
    out.memory_peak_bytes = peak_bytes(used)
    out.setup_s = probe.t_open - t_start
    out.window_s = probe.t_close - probe.t_open
    out.steps = probe.window_steps()
    out.counts = [probe.step_counts[n] for n in out.steps]
    out.compiles_in_window = probe.compiles_close - probe.compiles_open
    out.batches = [probe.batches[k] for k in range(3)]
    out.program = {"batches": out.batches,
                   "losses": [probe.losses[k] for k in range(3)],
                   "mean_logp": [probe.mean_logp[k] for k in range(3)],
                   "grad_norm": [probe.grad_norm[k] for k in range(3)],
                   "moments": [probe.moments[k] for k in range(3)],
                   "change": probe.change}
    # free the program's device state before the reference runs
    probe.ctl = probe.trainer = None
    del ctl
    gc.collect()
    out.live_bytes_after_release = sum(x.nbytes for x in jax.live_arrays())
    if check:
        with jax.default_device(used[0]):
            out.reference = replay.follow(
                ref, cell.config, seed, out.batches, lr=args.lr,
                rho=args.rho, n_per_prompt=args.n_per_prompt,
                prompt_len=args.prompt_len,
                rows_per_block=int(cell.traffic["rows_per_block"]))
    return out
