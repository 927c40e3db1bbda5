"""Production-style RL training launcher.

    PYTHONPATH=src python -m repro.launch.train \
        --arch starcoder2-3b --smoke --steps 50 --mode async --staleness 1

It builds the executors and channels for one config and runs the
single-controller loop.  From the command line every in-process executor
runs on the default device; a caller that hands ``build_controller`` a
``(trainer, generator)`` submesh pair (``launch/mesh.py``'s
``trainer_generator_submeshes``, paper Def. 7.4's theta split) places the
trainer and the generators on disjoint devices, with weights moving
between them by DDMA resharding (``chip_smoke.py --chips 4`` runs that
split).  ``--smoke`` runs the reduced config -- same code path, same
executors.  ``main`` turns on JAX's persistent compilation cache
(``enable_compile_cache``).

``--transport proc`` hosts the trainer, every pool generator and (with
--kl-coef) the frozen reference each in their own spawned process with a
private XLA client -- the paper's fully-distributed placement, one flag
away from the colocated thread run; the rule-based reward stays in the
controller process (lightweight python, as in the paper's Fig. 1).
``--transport shm`` is the same placement with weight- and batch-sized
payloads moving over shared-memory rings instead of pipe copies (the
DDMA-style data plane).  ``--transport socket`` goes multi-host: run

    python -m repro.launch.train --listen 0.0.0.0:9001 --host-devices 4

on each generator machine, then point the controller at them with
``--connect host1:9001,host2:9001`` -- actors are assigned trainer
first, then pool generators, then the reference, and any actor beyond
the list self-hosts on localhost.  ``--child-devices``/``--child-mesh``
give every spawned child its own emulated device world and submesh (a
remote actor pins its own XLA device set).
"""
from __future__ import annotations

import argparse
import json
import os

import jax
import jax.numpy as jnp

from repro import configs
from repro.core import (AdaptiveStalenessController, CommType,
                        CommunicationChannel, DeviceSpec,
                        ExecutorController, RewardExecutor, TrainerExecutor,
                        WeightsCommunicationChannel, build_generator_pool,
                        close_all_actors, spawn_actor)
from repro.obs import trace as obs_trace
from repro.rl.data import ArithmeticTasks, VOCAB_SIZE

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def _parse_addr(s: str):
    host, _, port = s.strip().rpartition(":")
    return (host or "0.0.0.0", int(port))


def _parse_mesh(s: str):
    """'1x4' -> (1, 4)."""
    return tuple(int(p) for p in s.lower().split("x")) if s else ()


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; call from entry points
    only, never at import.  ``JAX_COMPILATION_CACHE_DIR``, when set, is
    used as is; otherwise the cache is ``<repo>/.jax_cache`` -- a fixed
    path, since the path is part of the cache key -- exported so that
    spawned children share it.  Returns the directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(REPO_ROOT, ".jax_cache")
        os.environ["JAX_COMPILATION_CACHE_DIR"] = path
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def build_controller(cfg, args, meshes=None):
    """``meshes``: optional ``(trainer_mesh, generator_mesh)``; every
    executor otherwise runs on the default device.  Submeshes are live
    device objects, so they need the in-process transport."""
    n_gens = max(1, args.n_generators)
    if args.mode == "sync" or args.sequential:
        assert n_gens == 1, "--n-generators > 1 needs mode=async threads"
    trn_kw, gen_kw = {}, {}
    if meshes is not None:
        if args.transport != "inproc":
            raise ValueError("submeshes need transport='inproc' (got "
                             f"{args.transport!r})")
        trn_kw["mesh"], gen_kw["mesh"] = meshes
    spec = None
    if args.child_devices or args.child_mesh:
        spec = DeviceSpec(device_count=args.child_devices,
                          mesh_shape=_parse_mesh(args.child_mesh))
    # --connect addresses are consumed trainer-first, then generators,
    # then the reference; actors beyond the list self-host on localhost
    addrs = [_parse_addr(a) for a in args.connect.split(",")
             if a.strip()] if args.connect else []
    trn = spawn_actor(TrainerExecutor, cfg, lr=args.lr, rho=args.rho,
                      clip_mode=args.clip_mode, kl_coef=args.kl_coef,
                      seed=args.seed, transport=args.transport,
                      device_spec=spec,
                      address=addrs[0] if addrs else None, **trn_kw)
    gens, channels = build_generator_pool(
        cfg, trn,
        lambda g: ArithmeticTasks(prompt_len=args.prompt_len,
                                  max_operand=args.max_operand, ops="+-",
                                  seed=args.seed + g),
        n_generators=n_gens, seed=args.seed, n_prompts=args.n_prompts,
        n_per_prompt=args.n_per_prompt, max_new=args.max_new,
        temperature=args.temp, quantize=args.quantize_generator,
        chunk=args.rollout_chunk, transport=args.transport,
        device_spec=spec, addresses=addrs[1:1 + n_gens], **gen_kw)
    rew = RewardExecutor(n_per_prompt=args.n_per_prompt,
                         leave_one_out=args.rloo)
    executors = gens + [rew, trn]
    if args.kl_coef > 0:
        # paper Sec. 6: KL regularization against a frozen reference policy
        from repro.core import RefPolicyExecutor
        ref = spawn_actor(RefPolicyExecutor, cfg, transport=args.transport,
                          device_spec=spec,
                          address=addrs[1 + n_gens]
                          if len(addrs) > 1 + n_gens else None)
        executors.insert(len(gens), ref)
        channels += [
            WeightsCommunicationChannel("policy_model", trn, ref),
            CommunicationChannel("completions", gens[0], ref,
                                 CommType.BROADCAST),
            CommunicationChannel("completions_with_ref", ref, rew,
                                 CommType.GATHER),
        ]
    else:
        channels.append(CommunicationChannel("completions", gens[0], rew,
                                             CommType.GATHER))
    channels.append(CommunicationChannel("completions_with_reward", rew,
                                         trn, CommType.SCATTER))
    adaptive = None
    if args.adaptive_staleness > 0:
        assert args.mode == "async" and not args.sequential, \
            "--adaptive-staleness only acts on the threaded async loop"
        assert args.adaptive_staleness >= args.staleness, \
            f"--adaptive-staleness ({args.adaptive_staleness}) is the " \
            f"max bound and must be >= --staleness ({args.staleness})"
        adaptive = AdaptiveStalenessController(
            bound=args.staleness, min_bound=1,
            max_bound=args.adaptive_staleness)
    supervise = None
    if args.supervise or args.chaos:
        from repro.core import FaultPlan, RestartPolicy, Supervisor
        chaos = FaultPlan.parse(args.chaos) if args.chaos \
            else FaultPlan.from_env()
        supervise = Supervisor(
            RestartPolicy(max_restarts=args.max_restarts), chaos=chaos)
    pool = None
    if args.engine:
        from repro.core import PoolConfig
        assert args.mode == "async" and not args.sequential, \
            "--engine needs the threaded async loop (mode=async)"
        assert args.rollout_chunk > 0, \
            "--engine decodes in rounds: set --rollout-chunk >= 1"
        pool = PoolConfig(engine=True,
                          max_running_rows=args.max_running_rows,
                          kv_layout=args.kv_layout,
                          kv_page_size=args.kv_page_size,
                          kv_pages=args.kv_pages)
    return ExecutorController(
        executors, channels,
        max_steps=args.steps, mode=args.mode, staleness=args.staleness,
        checkpoint_every=args.checkpoint_every,
        checkpoint_path=args.checkpoint_path, adaptive=adaptive,
        overlap_publish=not args.no_overlap_publish, supervise=supervise,
        pool=pool)


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="starcoder2-3b",
                    choices=configs.list_archs() + ["llama31-8b"])
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU dev box)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--mode", default="async", choices=["sync", "async"])
    ap.add_argument("--staleness", type=int, default=1)
    ap.add_argument("--clip-mode", default="aipo",
                    choices=["aipo", "ppo", "none", "is_unclipped"])
    ap.add_argument("--rho", type=float, default=4.0)
    ap.add_argument("--kl-coef", type=float, default=0.0)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--n-prompts", type=int, default=8)
    ap.add_argument("--n-per-prompt", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=12)
    ap.add_argument("--max-operand", type=int, default=20)
    ap.add_argument("--temp", type=float, default=1.0)
    ap.add_argument("--rloo", action="store_true")
    ap.add_argument("--quantize-generator", action="store_true")
    ap.add_argument("--rollout-chunk", type=int, default=0)
    ap.add_argument("--engine", action="store_true",
                    help="continuous-batching rollout engine: row-level "
                    "admission into an in-flight slot pool, rows "
                    "harvested at EOS, groups emitted the moment they "
                    "complete (needs --rollout-chunk)")
    ap.add_argument("--max-running-rows", type=int, default=0,
                    help="engine slot-pool size (0 = 2x one batch's rows)")
    ap.add_argument("--kv-layout", default="",
                    choices=["", "dense", "paged"],
                    help="engine KV layout: paged = shared page arena + "
                    "per-row page tables + radix prefix reuse "
                    "(default: $REPRO_KV_LAYOUT, then dense)")
    ap.add_argument("--kv-page-size", type=int, default=0,
                    help="tokens per KV page (0 = 16)")
    ap.add_argument("--kv-pages", type=int, default=0,
                    help="KV arena pages shared by all rows (0 = every "
                    "slot fits a full row, i.e. no admission "
                    "backpressure; smaller = backpressure, not OOM)")
    ap.add_argument("--n-generators", type=int, default=1,
                    help="generator pool size (async mode): worker i "
                    "produces batches i, i+N, ... into the sample queue")
    ap.add_argument("--transport", default=None,
                    choices=["inproc", "proc", "shm", "socket"],
                    help="actor placement: 'inproc' runs every executor "
                    "on controller threads in this process; 'proc' hosts "
                    "trainer/generators/reference each in a spawned "
                    "subprocess with its own XLA client; 'shm' is proc "
                    "with weight/batch payloads over shared-memory rings "
                    "(the DDMA-style data plane); 'socket' speaks the "
                    "same wire format over TCP to --connect hosts or "
                    "local self-hosted helpers (default: "
                    "$REPRO_TRANSPORT or inproc)")
    ap.add_argument("--listen", default="",
                    help="actor-host mode: serve executors to a remote "
                    "controller on HOST:PORT and never train locally "
                    "(pairs with a controller running --transport socket "
                    "--connect THIS_HOST:PORT)")
    ap.add_argument("--connect", default="",
                    help="comma-separated HOST:PORT actor hosts for "
                    "--transport socket, assigned trainer first, then "
                    "pool generators, then the reference; actors beyond "
                    "the list self-host on localhost")
    ap.add_argument("--host-devices", type=int, default=0,
                    help="with --listen: emulated host device count for "
                    "this actor host (sets XLA_FLAGS before the backend "
                    "initializes)")
    ap.add_argument("--child-devices", type=int, default=0,
                    help="emulated device count for every spawned child "
                    "actor (proc/shm/self-hosted socket): each child "
                    "pins its own XLA device set")
    ap.add_argument("--child-mesh", default="",
                    help="mesh shape (e.g. '1x4') built from each "
                    "child's own devices and passed as its mesh=")
    ap.add_argument("--no-overlap-publish", action="store_true",
                    help="publish weights on the consumer thread "
                    "(blocking fan-out) instead of the weight fabric's "
                    "background publisher -- the Table-4-style baseline")
    ap.add_argument("--adaptive-staleness", type=int, default=0,
                    help="if > 0, the max bound for the adaptive "
                    "staleness controller (starts at --staleness, moves "
                    "in [1, max]; the async loop floors the bound at 1)")
    ap.add_argument("--supervise", action="store_true",
                    help="supervised (elastic) run: a dead generator or "
                    "reference actor is respawned from its spawn spec "
                    "with the latest committed weights replayed, within "
                    "a capped-backoff restart budget; when the budget "
                    "runs out the pool degrades to the survivors "
                    "(default: fail fast on the first ActorDied)")
    ap.add_argument("--max-restarts", type=int, default=3,
                    help="per-actor restart budget for --supervise")
    ap.add_argument("--chaos", default="",
                    help="deterministic fault injection spec (implies "
                    "supervision), e.g. 'kill:generator1@batch=2;"
                    "hang:generator0@batch=4:30'; also read from "
                    "$REPRO_CHAOS when --supervise is set")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--checkpoint-every", type=int, default=0)
    ap.add_argument("--checkpoint-path", default="checkpoints")
    ap.add_argument("--trace", default="",
                    help="export a Chrome-trace/Perfetto JSON of the run "
                    "to this path: spans from the controller, pool "
                    "workers, fabric and every spawned actor process on "
                    "one aligned timeline (open in ui.perfetto.dev; "
                    "summarize with 'python -m repro.obs PATH')")
    ap.add_argument("--sequential", action="store_true",
                    help="run the async schedule on one thread (debug "
                    "reference; numerically identical, no overlap)")
    ap.add_argument("--out", default="")
    return ap.parse_args(argv)


def main():
    args = parse_args()
    enable_compile_cache()

    if args.trace:
        # before any actor spawns: spawned children read the boot flag,
        # and the env covers anything forked outside the boot path
        os.environ.setdefault(obs_trace.ENV_FLAG, "1")
        obs_trace.enable("controller")

    if args.listen:
        # actor-host mode: this process owns its own device world and
        # serves one executor per inbound connection until killed.  The
        # XLA backend has not initialized yet (imports are lazy about
        # devices), so the device-count flag still takes effect.
        if args.host_devices:
            DeviceSpec(device_count=args.host_devices).apply_env()
        from repro.core import serve_actor_host
        host, port = _parse_addr(args.listen)
        print(f"actor host listening on {host}:{port} "
              f"(devices={args.host_devices or 'inherited'})", flush=True)
        serve_actor_host(host, port)
        return

    if args.arch == "llama31-8b":
        from repro.configs.llama_paper import LLAMA31_8B, smoke
        cfg = smoke() if args.smoke else LLAMA31_8B
    else:
        cfg = (configs.get_smoke(args.arch) if args.smoke
               else configs.get_config(args.arch))
    # the char tokenizer needs vocab >= VOCAB_SIZE; smoke configs have 512
    assert cfg.vocab >= VOCAB_SIZE, "config vocab too small for tokenizer"

    ctl = build_controller(cfg, args)
    try:
        history = ctl.run_sequential() if args.sequential and \
            args.mode == "async" else ctl.run()
    finally:
        close_all_actors()               # join process-backed executors
    for h in history:
        print({k: (round(v, 4) if isinstance(v, float) else v)
               for k, v in h.items()})
    print("stats:", {k: round(v, 3) for k, v in ctl.stats.items()})
    print("staleness_hist:", dict(sorted(ctl.staleness_hist.items())))
    if args.trace:
        from repro.obs.__main__ import summary_lines
        events = obs_trace.tracer().events()
        obs_trace.export(args.trace, events=events, metadata={
            "mode": args.mode, "steps": args.steps,
            "transport": args.transport or
            os.environ.get("REPRO_TRANSPORT", "inproc"),
            "n_generators": args.n_generators})
        print(f"trace: wrote {args.trace}")
        for line in summary_lines(events):
            print(line)
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"history": history, "stats": ctl.stats,
                       "staleness_hist": dict(ctl.staleness_hist)}, f,
                      indent=1)


if __name__ == "__main__":
    main()
