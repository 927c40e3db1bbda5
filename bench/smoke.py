"""Cells cut to a size the CPU tests can run: the same configuration file
with small widths, the same traffic file with short rows.  Only the tests
use these; a benchmark run never does."""
from __future__ import annotations

from bench import spec as bspec

SMALL = dict(n_layers=2, d_model=256, n_heads=8, n_kv_heads=2, head_dim=32,
             d_ff=512, vocab=512, window=64)
SHORT = {"decode-long": {"--max-new": "40", "--rollout-chunk": "8"},
         "prefill-train": {"--prompt-len": "64", "--max-new": "8",
                           "--rollout-chunk": "8"},
         "split4": {"--max-new": "16", "--rollout-chunk": "8"}}


def smoke_cell(name: str) -> bspec.Cell:
    """The cell ``name`` from BENCHMARK.json at the smoke size."""
    cell = bspec.load_cell(name)
    cfg = dict(cell.config, **SMALL)
    cfg["reduced"] = sorted(set(cfg["reduced"]) | set(SMALL))
    traffic = dict(cell.traffic)
    argv = list(traffic["argv"])
    for i in range(len(argv) - 1):
        argv[i + 1] = SHORT[traffic["name"]].get(argv[i], argv[i + 1])
    traffic["argv"] = argv
    cell.config, cell.traffic = cfg, traffic
    return cell
