import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede any jax import (jax locks the device count on first init).

"""Multi-pod dry-run: lower + compile every (arch x input-shape) combo on the
production meshes, and extract the roofline terms from the compiled HLO.

  python -m repro.launch.dryrun --arch deepseek-67b --shape train_4k
  python -m repro.launch.dryrun --all --mesh pod1 --out experiments/dryrun

Per combo this prints/records:
  * memory_analysis(): bytes per device (proves/refutes HBM fit)
  * cost_analysis(): HLO FLOPs + bytes accessed
  * collective bytes parsed from the compiled HLO text
  * the three roofline terms vs. TPU v5e peak numbers
"""
import argparse
import json
import re
import sys
import time
from typing import Dict

import jax
import jax.numpy as jnp

from repro import configs
from repro.configs.base import INPUT_SHAPES, param_count
from repro.launch.inputspecs import input_specs
from repro.launch.mesh import make_mesh, make_production_mesh
from repro.models.sharding import (activation_sharding, batch_shardings,
                                   cache_shardings, params_shardings,
                                   state_shardings)

# TPU v5e hardware constants (per chip)
PEAK_FLOPS = 197e12        # bf16
HBM_BW = 819e9             # bytes/s
ICI_BW = 50e9              # bytes/s/link (three 2D-torus links per chip)
HBM_BYTES = 16e9

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1, "u64": 8, "u32": 4, "u16": 2,
    "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLL_RE = re.compile(
    r"=\s*((?:\([^)]*\))|(?:[a-z0-9]+\[[0-9,]*\][^ ]*))\s*"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(", re.M)
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum result bytes of every collective op (per-device program)."""
    out: Dict[str, int] = {}
    for type_str, op in _COLL_RE.findall(hlo_text):
        out[op] = out.get(op, 0) + _shape_bytes(type_str)
    return out


def lower_combo(arch: str, shape_name: str, mesh, *,
                dtype=jnp.bfloat16, moe_mode: str = "gathered",
                remat: bool = True, unroll: bool = True,
                scan_group: int = 1, prefill_out_shardings: bool = False,
                accum_steps: int = 1, seq_parallel: bool = False):
    """Build the right step function + shardings, lower, compile.

    unroll_scans=True so cost_analysis counts every scan iteration (XLA
    counts loop bodies once); remat_layers=True is the realistic training
    baseline (the no-remat variant's temp bytes explode -- see Sec Perf)."""
    cfg = configs.get_config(arch).replace(
        unroll_scans=unroll, remat_layers=remat, moe_mode=moe_mode,
        scan_group=scan_group)
    shape = INPUT_SHAPES[shape_name]
    specs = input_specs(cfg, shape, dtype)

    if shape.kind == "train":
        from repro.train.trainstep import init_train_state, make_train_step
        state_shapes = jax.eval_shape(
            lambda: init_train_state(cfg, jax.random.PRNGKey(0), dtype))
        st_sh = state_shardings(state_shapes, mesh)
        b_sh = batch_shardings(specs["batch"], mesh)
        step = make_train_step(cfg, accum_steps=accum_steps)
        fn = jax.jit(step, in_shardings=(st_sh, b_sh),
                     out_shardings=(st_sh, None))
        with activation_sharding(mesh, seq_parallel=seq_parallel):
            lowered = fn.lower(state_shapes, specs["batch"])
    elif shape.kind == "prefill":
        from repro.models.backbone import init_params
        from repro.models.serve import prefill
        p_shapes = jax.eval_shape(
            lambda: init_params(cfg, jax.random.PRNGKey(0), dtype))
        p_sh = params_shardings(p_shapes, mesh, mode="serve")
        b_sh = batch_shardings(specs["batch"], mesh)

        def fn(params, batch):
            return prefill(params, cfg, batch, cache_len=shape.seq_len,
                           dtype=dtype)

        out_sh = None
        if prefill_out_shardings:
            # anchor the returned KV cache: without this GSPMD replicates
            # the [L,B,S,K,hd] stacks and all-reduces them (see Sec Perf)
            out_shapes = jax.eval_shape(fn, p_shapes, specs["batch"])
            out_sh = (batch_shardings(
                {"lg": out_shapes[0]}, mesh)["lg"],
                cache_shardings(out_shapes[1], mesh))
        with activation_sharding(mesh):
            lowered = jax.jit(fn, in_shardings=(p_sh, b_sh),
                              out_shardings=out_sh).lower(
                p_shapes, specs["batch"])
    else:  # decode
        from repro.models.backbone import init_params
        from repro.models.serve import decode_step
        p_shapes = jax.eval_shape(
            lambda: init_params(cfg, jax.random.PRNGKey(0), dtype))
        p_sh = params_shardings(p_shapes, mesh, mode="serve")
        c_sh = cache_shardings(specs["cache"], mesh)
        t_sh = batch_shardings({"t": specs["tokens"]}, mesh)["t"]

        def fn(params, cache, tokens):
            return decode_step(params, cfg, cache, tokens)

        with activation_sharding(mesh):
            lowered = jax.jit(fn, in_shardings=(p_sh, c_sh, t_sh)).lower(
                p_shapes, specs["cache"], specs["tokens"])
    return cfg, shape, lowered


def analyse(cfg, shape, lowered, mesh) -> Dict:
    t0 = time.time()
    compiled = lowered.compile()
    compile_s = time.time() - t0
    n_chips = mesh.devices.size
    cost = compiled.cost_analysis()
    mem = compiled.memory_analysis()
    hlo = compiled.as_text()
    colls = collective_bytes(hlo)
    coll_total = sum(colls.values())

    flops = float(cost.get("flops", 0.0))
    bytes_acc = float(cost.get("bytes accessed", 0.0))
    terms = {
        "compute_s": flops / PEAK_FLOPS,
        "memory_s": bytes_acc / HBM_BW,
        "collective_s": coll_total / ICI_BW,
    }
    dominant = max(terms, key=terms.get)
    total, active = param_count(cfg)
    tokens = shape.global_batch * (1 if shape.kind == "decode"
                                   else shape.seq_len)
    mult = 6 if shape.kind == "train" else 2
    model_flops = mult * active * tokens          # global useful FLOPs
    hlo_flops_global = flops * n_chips            # flops is per-device
    rec = {
        "arch": cfg.name, "shape": shape.name, "kind": shape.kind,
        "mesh": list(mesh.devices.shape), "n_chips": n_chips,
        "compile_s": round(compile_s, 1),
        "flops_per_device": flops,
        "bytes_per_device": bytes_acc,
        "collective_bytes_per_device": coll_total,
        "collectives": colls,
        "argument_bytes": mem.argument_size_in_bytes,
        "output_bytes": mem.output_size_in_bytes,
        "temp_bytes": mem.temp_size_in_bytes,
        "peak_bytes_per_device": (mem.argument_size_in_bytes
                                  + mem.temp_size_in_bytes),
        "fits_hbm": (mem.argument_size_in_bytes + mem.temp_size_in_bytes)
        < HBM_BYTES,
        "roofline": terms,
        "dominant": dominant,
        "model_flops_global": model_flops,
        "useful_flops_ratio": model_flops / max(hlo_flops_global, 1.0),
    }
    return rec


def _extrapolate(rec1, rec2, cfg, kind, seq_len=0):
    """True totals from counted-layer deltas (u=1 vs u=2 compiles)."""
    from repro.models.backbone import counted_layers, real_layers
    k = "decode" if kind == "decode" else ("train" if kind == "train"
                                           else "prefill")
    sl = seq_len if kind == "train" else 0
    c1 = counted_layers(cfg, 1, k, sl)
    c2 = counted_layers(cfg, 2, k, sl)
    real = real_layers(cfg, k, sl)
    scale = (real - c1) / max(c2 - c1, 1) if c2 > c1 else 0.0
    out = dict(rec1)
    for key in ("flops_per_device", "bytes_per_device",
                "collective_bytes_per_device"):
        out[key] = rec1[key] + (rec2[key] - rec1[key]) * scale
    out["collectives"] = {
        op: rec1["collectives"].get(op, 0)
        + (rec2["collectives"].get(op, 0)
           - rec1["collectives"].get(op, 0)) * scale
        for op in set(rec1["collectives"]) | set(rec2["collectives"])}
    out["counted_layers"] = [c1, c2, real]
    terms = {
        "compute_s": out["flops_per_device"] / PEAK_FLOPS,
        "memory_s": out["bytes_per_device"] / HBM_BW,
        "collective_s": out["collective_bytes_per_device"] / ICI_BW,
    }
    out["roofline"] = terms
    out["dominant"] = max(terms, key=terms.get)
    n_chips = rec1["n_chips"]
    out["useful_flops_ratio"] = out["model_flops_global"] / max(
        out["flops_per_device"] * n_chips, 1.0)
    return out


def run_combo(arch, shape_name, mesh_name, out_dir=None, roofline=True,
              variant="", mesh_shape=None, **kw):
    if mesh_shape:
        mesh = make_mesh(mesh_shape, ("data", "model"))
    else:
        mesh = make_production_mesh(multi_pod=(mesh_name == "pod2"))
    cfg, shape, lowered = lower_combo(arch, shape_name, mesh, **kw)
    rec = analyse(cfg, shape, lowered, mesh)
    if roofline:
        # second compile with 2-layer scan bodies isolates per-layer cost
        cfg2, _, lowered2 = lower_combo(arch, shape_name, mesh,
                                        scan_group=2, **kw)
        rec2 = analyse(cfg2, shape, lowered2, mesh)
        rec = _extrapolate(rec, rec2, cfg, shape.kind, shape.seq_len)
    rec["mesh_name"] = mesh_name
    line = (f"{arch:24s} {shape_name:12s} {mesh_name}  "
            f"C={rec['roofline']['compute_s']:.4f}s "
            f"M={rec['roofline']['memory_s']:.4f}s "
            f"X={rec['roofline']['collective_s']:.4f}s "
            f"dom={rec['dominant'][:4]} "
            f"peak={rec['peak_bytes_per_device']/1e9:.1f}GB "
            f"useful={rec['useful_flops_ratio']:.2f} "
            f"compile={rec['compile_s']}s")
    print(line, flush=True)
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        tag = f"_{variant}" if variant else ""
        fname = f"{arch}_{shape_name}_{mesh_name}{tag}.json"
        with open(os.path.join(out_dir, fname), "w") as f:
            json.dump(rec, f, indent=1)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="pod1", choices=["pod1", "pod2"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--variant", default="")
    ap.add_argument("--moe-mode", default="gathered",
                    choices=["gathered", "ep_shmap"])
    ap.add_argument("--prefill-out-shardings", action="store_true")
    ap.add_argument("--accum-steps", type=int, default=1)
    ap.add_argument("--seq-parallel", action="store_true")
    ap.add_argument("--mesh-shape", default="",
                    help="e.g. 8x32 (overrides --mesh pod1)")
    args = ap.parse_args()

    if args.all:
        failures = []
        for arch, shape_name in configs.combos():
            try:
                run_combo(arch, shape_name, args.mesh, args.out,
                          roofline=(args.mesh == "pod1"),
                          remat=not args.no_remat)
            except Exception as e:  # noqa: BLE001
                failures.append((arch, shape_name, str(e)[:200]))
                print(f"FAIL {arch} {shape_name}: {e}", flush=True)
        if failures:
            print(f"{len(failures)} failures"); sys.exit(1)
        print("ALL COMBOS LOWERED + COMPILED OK")
    else:
        ms = tuple(int(x) for x in args.mesh_shape.split("x")) \
            if args.mesh_shape else None
        run_combo(args.arch, args.shape, args.mesh, args.out,
                  roofline=(args.mesh == "pod1"),
                  remat=not args.no_remat, variant=args.variant,
                  moe_mode=args.moe_mode, mesh_shape=ms,
                  prefill_out_shardings=args.prefill_out_shardings,
                  accum_steps=args.accum_steps,
                  seq_parallel=args.seq_parallel)


if __name__ == "__main__":
    main()
