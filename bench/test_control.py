"""The control -- the reference in bfloat16, the precision below the
configuration's float32, put in the program's place -- at the smoke size
on the CPU.  The chip readings at the cells' own sizes, which set the
limits and show the control failing there on every seed, are in PERF.md.

Here the program's float32 is exact, so on every compared number the
control reads at least what the program reads and on one of them a
hundred times more; the program passes each cell's limits; and in the
decode cell, whose separating number (``logp_tail``) keeps its scale at
the smoke size, the control comes out not correct."""
import jax
import pytest

from bench import compare, control, smoke, spec as bspec

CELLS = [w["name"] for w in bspec.load_benchmark()["workloads"]]


def _readings(name, seed=7):
    cell = smoke.smoke_cell(name)
    if cell.traffic["placement"] == "split" and len(jax.devices()) < 4:
        cell.traffic = dict(cell.traffic, placement="colocated")
    return cell, control.readings_for_seed(cell, seed, 0.5,
                                           require_chip=False)


@pytest.mark.parametrize("name", CELLS)
def test_control_reads_above_the_program(name):
    cell, rec = _readings(name)
    prog, ctrl = rec["program"], rec["control"]
    assert compare.judge(prog, cell.limits)[0], rec
    compared = [n for n in compare.NAMES
                if n in cell.limits and prog[n] is not None]
    assert all(ctrl[n] >= prog[n] for n in compared), rec
    assert any(ctrl[n] >= 100 * prog[n] > 0 for n in compared), rec


def test_control_is_not_correct_in_the_decode_cell():
    cell, rec = _readings("sc2-3b.decode-long")
    assert not compare.judge(rec["control"], cell.limits)[0], rec
