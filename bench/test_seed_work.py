"""Every seed does the same work: each cell's traffic, at the smoke size,
run at two seeds with the benchmark's weights.  Every step trains the
same positions, no row ends before its budget, and the end and pad ids
are never drawn.  (On a CPU with one device the split cell's traffic
runs colocated: the work is the traffic's and the weights', not the
placement's.)"""
import time

import jax
import pytest

from bench import harness, smoke, spec as bspec

CELLS = [w["name"] for w in bspec.load_benchmark()["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_two_seeds_do_the_same_work(name):
    cell = smoke.smoke_cell(name)
    if cell.traffic["placement"] == "split" and len(jax.devices()) < 4:
        cell.traffic = dict(cell.traffic, placement="colocated")
    per_seed = []
    for seed in (101, 2**32 + 7):
        out = harness.run_cell(cell, seed, 0.5, False,
                               t_start=time.perf_counter(),
                               require_chip=False, check=False)
        counts = [out.probe.step_counts[n]
                  for n in sorted(out.probe.step_counts)]
        assert counts, "no step trained"
        for c in counts:
            assert c["rows_ended_by_end_id"] == 0
            assert c["pad_draws"] == 0
            assert c["positions_trained"] == out.probe.declared
        per_seed.append([(c["positions_decoded"], c["positions_trained"])
                         for c in counts])
    n = min(len(s) for s in per_seed)
    assert per_seed[0][:n] == per_seed[1][:n]
