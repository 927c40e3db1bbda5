"""Roofline report (deliverable g): reads the dry-run JSONs and prints the
per-(arch x shape) three-term table with dominant bottleneck + useful-flops
ratio + a one-line 'what would move the dominant term' note.

Run the dry-run first:  PYTHONPATH=src python -m repro.launch.dryrun --all
"""
from __future__ import annotations

import glob
import json
import os

from benchmarks.common import emit
from repro import configs
from repro.configs.base import INPUT_SHAPES, param_count

NOTES = {
    ("compute_s", "train"): "raise per-chip math: larger microbatch/"
    "less remat recompute or int8 matmuls",
    ("memory_s", "train"): "cut HBM traffic: fuse remat reads, bf16 "
    "optimizer states, flash-attention kernel (VMEM reuse)",
    ("memory_s", "prefill"): "flash kernel keeps scores in VMEM; "
    "shard KV-cache writes",
    ("memory_s", "decode"): "weights dominate: 2D-shard serve weights / "
    "int8 them; batch more decode streams",
    ("collective_s", "train"): "FSDP all-gathers dominate: bigger model "
    "axis, overlap collectives with compute, or replicate small params",
    ("collective_s", "decode"): "TP all-reduces per token: fuse, or "
    "shrink mp (paper Sec. 4.3)",
    ("compute_s", "decode"): "MoE experts computed where the tokens are: "
    "split them over the model axis (moe_mode=ep_shmap)",
    ("compute_s", "prefill"): "attention flops: windowed/blocksparse "
    "variants",
}


def load(out_dir="experiments/dryrun", mesh="pod1"):
    recs = []
    for f in sorted(glob.glob(os.path.join(out_dir, f"*_{mesh}.json"))):
        with open(f) as fh:
            recs.append(json.load(fh))
    return recs


def fixed_useful(rec):
    """Recompute useful-flops ratio (early runs mis-counted prefill)."""
    cfg = configs.get_config(rec["arch"])
    shape = INPUT_SHAPES[rec["shape"]]
    _, active = param_count(cfg)
    tokens = shape.global_batch * (1 if shape.kind == "decode"
                                   else shape.seq_len)
    mult = 6 if shape.kind == "train" else 2
    model = mult * active * tokens
    return model / max(rec["flops_per_device"] * rec["n_chips"], 1.0)


def main():
    recs = load()
    if not recs:
        emit("roofline/missing", 0.0, "run repro.launch.dryrun first")
        return
    for r in recs:
        t = r["roofline"]
        dom = r["dominant"]
        note = NOTES.get((dom, r["kind"]), "")
        u = fixed_useful(r)
        emit(f"roofline/{r['arch']}/{r['shape']}",
             t[dom] * 1e6,
             f"C={t['compute_s']:.3f};M={t['memory_s']:.3f};"
             f"X={t['collective_s']:.3f};dom={dom[:-2]};useful={u:.2f};"
             f"fits_hbm={r['fits_hbm']};note={note}")


if __name__ == "__main__":
    main()
