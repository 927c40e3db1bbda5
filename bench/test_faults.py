"""A run with the timed path broken underneath comes out not correct,
once for each fault the cells can have; the same run unbroken comes out
correct.  The harness's look for a chip is skipped; everything else is a
whole run at the smoke size, judged by the cell's own limits."""
import time

import numpy as np
import pytest

from bench import run as brun, smoke

SEED = 5


def _trainer(ctl):
    return ctl.trainer.transport.executor


def _generator(ctl):
    return ctl.generators[0].transport.executor


def state_unchanged(ctl):
    trn = _trainer(ctl)
    step = trn._jitted
    trn._jitted = lambda state, batch: (state, step(state, batch)[1])


def half_batch(ctl):
    trn = _trainer(ctl)
    step = trn._jitted

    def halved(state, batch):
        half = batch["tokens"].shape[0] // 2
        return step(state, {k: v[:half] for k, v in batch.items()})
    trn._jitted = halved


def exchange_left_out(ctl):
    gen = _generator(ctl)
    set_weights = gen.set_weights

    def stale(params, version=None):
        set_weights(params if gen.params is None else gen.params, version)
    gen.set_weights = stale


def token_altered(ctl):
    gen = _generator(ctl)
    engine_round = gen.engine_round

    def altered(names):
        items = engine_round(names)
        for item in items:
            comp = dict(item["snapshot"]["completions"])
            toks = np.array(comp["tokens"])
            col = comp["prompt_len"] + 1
            toks[:, col] = np.where(toks[:, col] == 5, 6, 5)
            comp["tokens"] = toks
            item["snapshot"] = dict(item["snapshot"], completions=comp)
        return items
    gen.engine_round = altered


def _run(plant=None):
    cell = smoke.smoke_cell("sc2-3b.decode-long")
    return brun.run_once(cell, SEED, 0.5, False, t_start=time.perf_counter(),
                         require_chip=False, plant=plant)


def test_sound_run_is_correct():
    res = _run()
    assert res["correct"], res["compared"]


@pytest.mark.parametrize("fault", [state_unchanged, half_batch,
                                   exchange_left_out, token_altered],
                         ids=lambda f: f.__name__)
def test_fault_is_not_correct(fault):
    res = _run(fault)
    assert not res["correct"], res["compared"]
