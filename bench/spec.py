"""Find a cell's pieces by name: ``BENCHMARK.json`` names the cell, and
the configuration, traffic mix, limits and metric readers each sit in a
file of their own under this directory.  Adding a cell means adding
files; nothing here changes."""
from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
REPO_ROOT = os.path.dirname(BENCH_DIR)


def _load_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    """One entry of ``workloads`` with everything it names loaded."""
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    limits: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    root: str = field(default=BENCH_DIR, repr=False)

    def metrics(self, trace: bool) -> List[Dict[str, Any]]:
        """The metrics this cell reports in a run with tracing on or off:
        a metric with a ``workloads`` key only where it lists the cell."""
        pool = self.per_layer if trace else self.end_to_end
        return [m for m in pool
                if "workloads" not in m or self.name in m["workloads"]]


def load_benchmark(repo_root: str = REPO_ROOT) -> Dict[str, Any]:
    return _load_json(os.path.join(repo_root, "BENCHMARK.json"))


def load_cell(name: str, bench: Optional[Dict[str, Any]] = None,
              root: str = BENCH_DIR) -> Cell:
    """Look ``name`` up in ``BENCHMARK.json`` and load the files it
    names: ``configs/<config>.json``, ``traffic/<traffic>.json`` and
    ``limits/<cell>.json`` under ``root``."""
    bench = bench if bench is not None else load_benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    return Cell(
        name=name, chips=int(w["chips"]),
        config=load_config(w["config"], root),
        traffic=load_traffic(w["traffic"], root),
        limits=_load_json(os.path.join(root, "limits", f"{name}.json")),
        end_to_end=bench["end_to_end"], per_layer=bench["per_layer"],
        root=root)


def load_config(name: str, root: str = BENCH_DIR) -> Dict[str, Any]:
    cfg = _load_json(os.path.join(root, "configs", f"{name}.json"))
    assert cfg["name"] == name, (cfg["name"], name)
    return cfg


def load_traffic(name: str, root: str = BENCH_DIR) -> Dict[str, Any]:
    t = _load_json(os.path.join(root, "traffic", f"{name}.json"))
    assert t["name"] == name, (t["name"], name)
    return t


def _load_module(path: str, modname: str):
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, root: str = BENCH_DIR) -> Callable:
    """``metrics/<name>.py``'s ``read(ctx)``: returns the metric's value,
    or None where the run holds nothing for it to read."""
    path = os.path.join(root, "metrics", f"{name}.py")
    return _load_module(path, f"bench_metric_{name.replace('.', '_')}").read


def reference_module(name: str, root: str = BENCH_DIR):
    """``references/<name>.py``: the plain float32 model a configuration
    names under ``reference``."""
    return _load_module(os.path.join(root, "references", f"{name}.py"),
                        f"bench_reference_{name}")
