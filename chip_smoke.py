#!/usr/bin/env python3
"""Bring-up check: the async RL loop on TPU v5e at StarCoder2-3B widths.

    python chip_smoke.py              # one chip: default and paged phases
    python chip_smoke.py --chips 4    # trainer/generator split, four chips
    JAX_PLATFORMS=cpu python chip_smoke.py --smoke   # CPU rehearsal

StarCoder2-3B at its published widths (d_model 3072, 24/2 heads, head_dim
128, d_ff 12288, vocab 49152), cut only in depth to ``N_LAYERS``, with
random weights from ``--seed``, is driven through the launcher's own
argument parser and ``build_controller``: async mode, staleness 1,
in-process actors.

One chip runs two phases:

* ``default`` -- the launcher's default traffic (8 prompts x 4 samples,
  8 new tokens) on the batch rollout path;
* ``paged`` -- the continuous-batching engine over the paged KV cache,
  with rows of 513 tokens, long enough that paged decode attention is
  routed to its kernel.

``--chips 4`` runs only the split and what it is compared with: the
trainer on the first two chips of ``trainer_generator_submeshes(0.5)``,
the generator on the other two, DDMA weight sync every step, against the
same seed and steps colocated on one chip.

Each phase checks that losses are finite, that batch ``i`` was generated
by weight version ``max(0, i - 1)`` (the engine may admit rows under a
newer one, never an older one), and that on the first on-policy
batch the trainer's log-probs agree with the generator's behavior
log-probs.  It prints setup facts -- compile and step times, peak device
memory, the backend each kernel hot path took -- not benchmark results.

Everything runs in this one process, which holds the chips; it starts no
child.  The last line, a JSON object naming the device, is printed only
when every check passed on a TPU.  ``--smoke`` runs the reduced config
(a CPU rehearsal of the same phases) and never prints it.
"""
import argparse
import gc
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "starcoder2-3b"
# The largest depth that fits one v5e chip (16 GiB) in fp32.  The train
# step holds params and both Adam moments as inputs and outputs: 9.56 GB
# at 1 layer, 12.01 GB at 2 (memory_analysis, compiled for a described
# v5e).  The 1-layer run peaks at 12.79 GB on a v5e: two param versions
# (1.6 GB each) beyond the train step.  At 2 layers that is 12.01 + 2 x
# 2.0 GB, which leaves under 1 GB for everything else.
N_LAYERS = 1
STALENESS = 1
# |mean_ratio - 1| on the first on-policy batch.  The generator's cached
# decode and the trainer's full-sequence forward run different programs
# over the same weights, and TPU fp32 matmuls multiply in bf16 by default
# (~3 significant digits), so per-token log-probs may differ by ~1e-2
# (the batch mean stayed within 1e-4 on a v5e); a mismatch of weights,
# tokens or positions moves the ratio by far more.
RATIO_TOL = 0.05
# Split vs colocated: the same seed, steps and programs on chips of one
# kind, so only the compiler's choices for a replicated program may
# reorder sums (fp32 rounding, ~1e-6).  A sampled token that differs
# moves the batch's mean log-prob by ~1e-2 relative: the bound catches it.
SPLIT_RTOL = 1e-3
WARMUP_STEPS = 2
STEPS = 3
HOT_PATHS = {"default": ("logprob", "sample"),
             "paged": ("logprob", "sample", "paged_attention")}


class CheckFailed(AssertionError):
    pass


def check(ok, what):
    if not ok:
        raise CheckFailed(what)
    print(f"  check ok: {what}")


def launcher_argv(seed, extra=()):
    # transport set explicitly so that REPRO_TRANSPORT cannot move actors
    # into child processes
    return ["--arch", ARCH, "--mode", "async", "--staleness",
            str(STALENESS), "--transport", "inproc", "--seed", str(seed),
            *extra]


def paged_argv(smoke):
    # 12-token prompts + 501 new tokens: 513-token rows span 33 pages of
    # 16, past the kernel's 512-position threshold, and 8 x 512 action
    # positions tile the logprob kernel's 256-row blocks without padding
    max_new, chunk = ("40", "8") if smoke else ("501", "64")
    return ["--engine", "--kv-layout", "paged", "--rollout-chunk", chunk,
            "--max-new", max_new, "--n-prompts", "2"]


def peak_bytes(dev):
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use", "not reported")


def run_phase(name, cfg, argv, meshes=None):
    """Warm up, then time ``STEPS`` more steps of one controller; both
    windows end in ``block_until_ready`` on the trainer's params."""
    import jax
    from repro.launch import train

    args = train.parse_args(argv + ["--steps", str(WARMUP_STEPS)])
    ctl = train.build_controller(cfg, args, meshes)
    try:
        t0 = time.perf_counter()
        ctl.run()
        jax.block_until_ready(ctl.trainer.call("get_model"))
        warm_s = time.perf_counter() - t0
        ctl.max_steps = STEPS
        t1 = time.perf_counter()
        history = ctl.run()
        jax.block_until_ready(ctl.trainer.call("get_model"))
        step_s = (time.perf_counter() - t1) / STEPS
    finally:
        ctl.shutdown()
    n_params = sum(x.size for x in
                   jax.tree.leaves(ctl.trainer.call("get_model")))
    print(f"[{name}] n_layers={cfg.n_layers} params={n_params} "
          f"d_model={cfg.d_model} heads={cfg.n_heads}/{cfg.n_kv_heads} "
          f"head_dim={cfg.head_dim} d_ff={cfg.d_ff} vocab={cfg.vocab}")
    print(f"[{name}] warm-up ({WARMUP_STEPS} steps, compile included): "
          f"{warm_s:.3f} s; steady step: {step_s:.4f} s "
          f"(mean of {STEPS}, wall clock to block_until_ready)")
    for h in history:
        print(f"[{name}] step {h['step']}: loss={h['loss']!r} "
              f"mean_logp={h['mean_logp']!r} "
              f"mean_ratio={h['mean_ratio']!r} "
              f"weight_version={h['weight_version']} "
              f"sample_staleness={h['sample_staleness']}")
    check_history(name, history)
    return ctl, history


def check_history(name, history):
    check(len(history) == WARMUP_STEPS + STEPS,
          f"{name}: {len(history)} steps recorded")
    check(all(math.isfinite(h["loss"]) for h in history),
          f"{name}: every loss is finite")
    if name == "paged":
        # the engine admits each row under the newest committed version:
        # a batch may be fresher than the schedule's floor, never staler
        check(all(max(0, h["step"] - STALENESS) <= h["weight_version"]
                  <= h["step"] for h in history),
              f"{name}: max(0, step - {STALENESS}) <= weight_version <= step")
    else:
        check(all(h["weight_version"] == max(0, h["step"] - STALENESS)
                  for h in history),
              f"{name}: weight_version == max(0, step - {STALENESS})")
    first = next(h for h in history if h["sample_staleness"] == 0)
    check(abs(first["mean_ratio"] - 1.0) <= RATIO_TOL,
          f"{name}: on-policy step {first['step']} mean_ratio "
          f"{first['mean_ratio']!r} within {RATIO_TOL} of 1")


def check_routes(name, on_tpu):
    from repro.kernels import dispatch
    routes = dispatch.routes_taken()
    print(f"[{name}] kernel routes (traces per backend): "
          f"{json.dumps(routes, sort_keys=True)}; paged-attention pages "
          f"per block (P, K, hd, max_blocks): {dispatch.paged_block_pages()}")
    if on_tpu:
        for path in HOT_PATHS[name]:
            check(set(routes.get(path, {})) == {"pallas_compile"},
                  f"{name}: {path} took only pallas_compile")


def report_release(name):
    """Call once the caller has dropped its last reference to a finished
    controller (its executors hold params and optimizer state): what
    stays live on the devices is what the next phase cannot have."""
    import jax
    gc.collect()
    live = sum(x.nbytes for x in jax.live_arrays())
    print(f"[{name}] device bytes still live after release: {live}")


def one_chip(cfg, dev, seed, smoke):
    on_tpu = dev.platform == "tpu"
    for name, extra in (("default", ()), ("paged", paged_argv(smoke))):
        ctl, _ = run_phase(name, cfg, launcher_argv(seed, extra))
        check_routes(name, on_tpu)
        print(f"[{name}] peak_bytes_in_use: {peak_bytes(dev)}")
        del ctl
        report_release(name)
        print(f"phase {name}: ok", flush=True)


def devices_of(tree):
    import jax
    return set().union(*(x.devices() for x in jax.tree.leaves(tree)
                         if isinstance(x, jax.Array)))


def four_chips(cfg, seed):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec
    from repro.core import ddma
    from repro.launch.mesh import trainer_generator_submeshes

    devs = jax.devices()
    check(len(devs) == 4, f"{len(devs)} devices visible")
    argv = launcher_argv(seed)
    ctl, colo = run_phase("colocated", cfg, argv)
    print("[colocated] devices: "
          f"{sorted(d.id for d in devices_of(ctl.trainer.call('state')))}")
    del ctl
    report_release("colocated")
    print("phase colocated: ok", flush=True)

    t_mesh, g_mesh = trainer_generator_submeshes(0.5)
    t_devs, g_devs = set(t_mesh.devices.flat), set(g_mesh.devices.flat)
    ctl, split = run_phase("split", cfg, argv, meshes=(t_mesh, g_mesh))
    print(f"[split] trainer on {sorted(d.id for d in t_devs)}, "
          f"generator on {sorted(d.id for d in g_devs)}")
    check(not t_devs & g_devs, "split: submeshes are disjoint")
    check(devices_of(ctl.trainer.call("state")) == t_devs,
          "split: trainer params and optimizer state live on the trainer "
          "submesh only")
    params = ctl.generator.call("params")
    check(devices_of(params) == g_devs,
          "split: generator params live on the generator submesh only")
    check(devices_of(ctl.generator.call("get_output", "completions"))
          == g_devs, "split: generated batches live on the generator "
          "submesh only")
    for version, secs in ctl._fabric.published:
        print(f"[split] publish v{version}: {secs:.4f} s (host time of "
              "the fabric's DDMA reshard; the copy itself is async)")
    target = NamedSharding(g_mesh, PartitionSpec())
    src = ctl.trainer.call("get_model")
    jax.block_until_ready(ddma.ddma_weight_sync(src, target))   # warm
    t0 = time.perf_counter()
    jax.block_until_ready(ddma.ddma_weight_sync(src, target))
    print(f"[split] one DDMA weight sync, trainer -> generator submesh, "
          f"to block_until_ready: {time.perf_counter() - t0:.4f} s")
    for a, b in zip(colo, split):
        for key in ("loss", "mean_logp"):
            check(abs(a[key] - b[key]) <= SPLIT_RTOL * abs(a[key]) + 1e-6,
                  f"step {a['step']}: split {key} {b[key]!r} matches "
                  f"colocated {a[key]!r} (rtol {SPLIT_RTOL})")
    check_routes("split", False)
    for d in devs:
        print(f"[split] device {d.id} peak_bytes_in_use: {peak_bytes(d)}")
    print("phase split: ok", flush=True)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4])
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config: a CPU rehearsal of the phases; "
                    "never reports success")
    ap.add_argument("--seed", type=int, default=0)
    opts = ap.parse_args()

    import jax
    from repro import configs
    from repro.launch import train

    print(f"compilation cache: {train.enable_compile_cache()}")
    dev = jax.devices()[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(jax.devices())}")
    if dev.platform != "tpu" and not opts.smoke:
        sys.exit(f"no TPU found (platform {dev.platform!r}); the full-width "
                 "run needs the chip -- use --smoke to rehearse on the CPU")
    cfg = (configs.get_smoke(ARCH) if opts.smoke
           else configs.get_config(ARCH).replace(n_layers=N_LAYERS))
    if opts.chips == 4:
        four_chips(cfg, opts.seed)
    else:
        one_chip(cfg, dev, opts.seed, opts.smoke)
    if opts.smoke or dev.platform != "tpu":
        sys.exit("rehearsal finished: every phase passed, but not at full "
                 "width on a TPU, so no result is reported")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
