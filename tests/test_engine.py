"""Continuous-batching rollout engine (ISSUE 9): per-row slot-pool
decode primitives, sequence-level admission, group-complete harvesting,
per-row bounded staleness, teardown hygiene, and supervised re-admission
of in-flight rows after a chaos kill.

The load-bearing correctness check is the behavior-logprob recompute:
every mu the engine emits must match a teacher-forced ``forward_train``
pass over the emitted tokens at the fixed weights -- if per-row cursors,
cache grafts, or zombie-slot clamping corrupted any KV entry, the decode
logits (and with them mu) would diverge.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from _hypothesis_compat import given, settings, st
from repro.core import (CommType, CommunicationChannel, ExecutorController,
                        FaultPlan, PoolConfig, RewardExecutor, Supervisor,
                        TrainerExecutor, build_generator_pool)
from repro.core.aipo import token_logprobs
from repro.core.executor import GeneratorExecutor
from repro.models import decode_step, forward_train, init_params
from repro.models.serve import SlotPool, assert_engine_cache
from repro.obs import metrics as obs_metrics
from repro.rl.data import PAD, ArithmeticTasks
from repro.rl.engine import GroupLedger, RolloutEngine
from repro.rl.rollout import (admit_row, rollout_rows_chunk, start_rollout,
                              start_row_pool)
from repro.rl.scheduler import RolloutScheduler, RowJob

from test_genpool import micro_cfg


def _params(cfg):
    return init_params(cfg, jax.random.PRNGKey(0), jnp.float32)


def _executor(chunk=2, n_prompts=2, n_per_prompt=2, max_new=4, seed=0):
    cfg = micro_cfg()
    ex = GeneratorExecutor(
        cfg, ArithmeticTasks(prompt_len=8, max_operand=9, ops="+", seed=seed),
        n_prompts=n_prompts, n_per_prompt=n_per_prompt, max_new=max_new,
        chunk=chunk, seed=seed)
    ex.set_weights(_params(cfg), version=0)
    return ex


# ----------------------------------------------- per-row decode primitives --

def test_vector_pos_decode_matches_scalar_pos():
    """A [B] per-row cursor vector with uniform entries must decode to
    the same logits as the scalar cursor it generalizes."""
    cfg = micro_cfg()
    params = _params(cfg)
    prompts = jnp.asarray([[1, 5, 6, 7], [1, 8, 9, 4]], jnp.int32)
    state = start_rollout(params, cfg, prompts, 8)
    toks = jnp.asarray([[3], [9]], jnp.int32)
    logits_s, cache_s = decode_step(params, cfg, state.cache, toks)
    vec = {**state.cache,
           "pos": jnp.full((2,), state.cache["pos"], jnp.int32)}
    logits_v, cache_v = decode_step(params, cfg, vec, toks)
    np.testing.assert_allclose(np.asarray(logits_v), np.asarray(logits_s),
                               rtol=0, atol=1e-6)
    assert np.asarray(cache_v["pos"]).shape == (2,)
    assert (np.asarray(cache_v["pos"]) ==
            int(np.asarray(cache_s["pos"]))).all()


def test_divergent_cursor_pool_matches_solo_decode():
    """Rows admitted at different times -- so the pool's cursors diverge
    -- must each decode exactly as the same row would alone (B=1, scalar
    cursor).  Teacher-forced tokens keep the comparison sampling-free."""
    cfg = micro_cfg()
    params = _params(cfg)
    T = 8
    pA = jnp.asarray([[1, 5, 6, 7]], jnp.int32)
    pB = jnp.asarray([[1, 9, 4, 8]], jnp.int32)
    donorA = start_rollout(params, cfg, pA, T, cache_len=T + 1)
    donorB = start_rollout(params, cfg, pB, T, cache_len=T + 1)
    pool = start_row_pool(cfg, 3, T, 4)
    pool = admit_row(pool, donorA, 0)

    # round 1: only row 0 live (rows 1, 2 are zombie free slots)
    tok1 = jnp.asarray([[7], [0], [0]], jnp.int32)
    logits1, cache1 = decode_step(params, cfg, pool.cache, tok1)
    sA1, cA = decode_step(params, cfg, donorA.cache, tok1[:1])
    np.testing.assert_allclose(np.asarray(logits1[0]), np.asarray(sA1[0]),
                               rtol=0, atol=1e-6)

    # admit row B into slot 2 mid-decode, then round 2 with both live
    pool = pool._replace(cache=cache1, last_logits=logits1)
    pool = admit_row(pool, donorB, 2)
    tok2 = jnp.asarray([[9], [0], [11]], jnp.int32)
    logits2, _ = decode_step(params, cfg, pool.cache, tok2)
    sA2, _ = decode_step(params, cfg, cA, tok2[:1])
    sB1, _ = decode_step(params, cfg, donorB.cache, tok2[2:])
    np.testing.assert_allclose(np.asarray(logits2[0]), np.asarray(sA2[0]),
                               rtol=0, atol=1e-6)
    np.testing.assert_allclose(np.asarray(logits2[2]), np.asarray(sB1[0]),
                               rtol=0, atol=1e-6)


def test_rows_chunk_leaves_unadmitted_rows_untouched():
    cfg = micro_cfg()
    params = _params(cfg)
    T = 8
    donor = start_rollout(
        params, cfg, jnp.asarray([[1, 5, 6, 7]], jnp.int32), T,
        cache_len=T + 1)
    pool = start_row_pool(cfg, 3, T, 4)
    pool = admit_row(pool, donor, 1)
    out = rollout_rows_chunk(params, cfg, pool, jax.random.PRNGKey(1),
                             n_steps=3)
    for r in (0, 2):
        assert (np.asarray(out.tokens[r]) == 0).all()
        assert (np.asarray(out.behavior_logp[r]) == 0).all()
        assert bool(out.done[r])


def test_slot_pool_acquire_release_cycle():
    sp = SlotPool(3)
    assert [sp.acquire() for _ in range(3)] == [0, 1, 2]
    assert sp.acquire() is None and sp.free_count == 0
    sp.release(1)
    assert sp.used == frozenset({0, 2}) and sp.acquire() == 1
    with pytest.raises(AssertionError):
        sp.release(2) or sp.release(2)


def test_engine_cache_contract_rejects_unsupported_families():
    assert_engine_cache(micro_cfg())          # dense, non-windowed: fine
    with pytest.raises(AssertionError):
        assert_engine_cache(micro_cfg().replace(attn_kind="mla"))


# --------------------------------------------------- engine end-to-end ----

def test_engine_emits_group_complete_batches_with_exact_mu():
    """Two batches through the engine: in-order emission, trainer-shaped
    output, per-row staleness contract intact, and mu matching a
    teacher-forced forward recompute at the fixed weights."""
    ex = _executor()
    ex.engine_configure(max_running_rows=8)
    ex.engine_enqueue(0, bound=1)
    ex.engine_enqueue(1, bound=1)
    items, rounds = [], 0
    while len(items) < 2 and rounds < 50:
        items += ex.engine_round(["completions"])
        rounds += 1
    assert [it["batch_index"] for it in items] == [0, 1]
    st_ = ex.engine_stats()
    assert st_["staleness_violations"] == 0
    assert st_["rows_harvested"] == st_["rows_enqueued"] == 8
    assert st_["waiting"] == 0 and st_["running"] == 0

    out = items[0]["snapshot"]["completions"]
    toks = np.asarray(out["tokens"])
    blp = np.asarray(out["behavior_logp"])
    mask = np.asarray(out["mask"])
    Sp = out["prompt_len"]
    assert toks.shape == (4, Sp + 4)
    ar = np.arange(toks.shape[1])[None, :]
    assert (mask == ((ar >= Sp) & (toks != PAD))).all()
    lag = out["version_floor"] - np.asarray(out["row_versions"])
    assert ((0 <= lag) & (lag <= 1)).all()

    logits, _ = forward_train(ex.params, ex.cfg, {"tokens": jnp.asarray(toks)})
    lp = np.asarray(token_logprobs(logits[:, :-1], jnp.asarray(toks[:, 1:])))
    recomputed = np.zeros_like(blp)
    recomputed[:, 1:] = lp
    np.testing.assert_allclose(blp * mask, recomputed * mask, atol=1e-4)

    # the emission feeds RewardExecutor unchanged, and the engine's eager
    # group-local advantages equal the batch-level recomputation
    rew = RewardExecutor(n_per_prompt=2)
    rew.put_input("completions", out)
    rew.step()
    adv = np.asarray(rew.get_output("completions_with_reward")["advantages"])
    np.testing.assert_allclose(
        adv, out["group_advantages"][:, None] * mask)


def test_engine_counters_advance_once_per_dispatched_round():
    """``engine.rounds`` counts each round that dispatched a chunk and
    ``engine.live_row_rounds`` the rows live at that dispatch; the
    ``engine.slots`` gauge holds the slot count.  A round with no live
    row dispatches nothing and counts nothing."""
    reg = obs_metrics.registry()
    ex = _executor(chunk=2, max_new=4)           # 2 chunks per row
    ex.engine_configure(max_running_rows=8)
    rounds = reg.counter("engine.rounds")
    live = reg.counter("engine.live_row_rounds")
    assert reg.gauge("engine.slots").value == 8
    r0, l0 = rounds.value, live.value
    ex.engine_round(["completions"])             # nothing enqueued
    assert (rounds.value, live.value) == (r0, l0)
    ex.engine_enqueue(0, bound=0)                # 4 rows
    ex.engine_round(["completions"])
    assert (rounds.value, live.value) == (r0 + 1, l0 + 4)
    items = ex.engine_round(["completions"])     # last chunk: harvested
    assert [it["batch_index"] for it in items] == [0]
    assert (rounds.value, live.value) == (r0 + 2, l0 + 8)
    ex.engine_round(["completions"])             # empty again
    assert (rounds.value, live.value) == (r0 + 2, l0 + 8)


def test_engine_charges_expert_tally_across_pool_restarts():
    """An expert model's pool tallies its products on the device and the
    harvest charges the differences to ``moe.*``: one product call per
    expert layer for each admission prefill and each decode step.  A
    pool started again after an abort charges from zero again."""
    from repro import configs
    reg = obs_metrics.registry()
    cfg = configs.get_smoke("mellum2-12b-a2.5b")
    ex = GeneratorExecutor(
        cfg, ArithmeticTasks(prompt_len=8, max_operand=9, ops="+", seed=0),
        n_prompts=2, n_per_prompt=2, max_new=4, chunk=2, seed=0)
    ex.set_weights(_params(cfg), version=0)
    ex.engine_configure(max_running_rows=4, kv_layout="paged",
                        kv_page_size=4)
    names = ("moe.pairs_held", "moe.experts_hit", "moe.products")
    before = [reg.counter(n).value for n in names]
    ex.engine_enqueue(0, bound=0)                # 4 rows fill the 4 slots
    items = []
    while not items:
        items = ex.engine_round(["completions"])
    pairs, hits, products = (reg.counter(n).value - b
                             for n, b in zip(names, before))
    # 4 prefills and 2 chunks of 2 decode steps over 2 expert layers;
    # the prefills run 8 prompt tokens, 4 for a sibling whose first page
    # the radix cache holds; each decode step runs the 4 slots
    assert products == 2 * (4 + 2 * 2)
    assert 0 < hits <= products * cfg.held_experts
    assert pairs == 2 * (8 + 4 + 8 + 4 + 4 * 4) * cfg.moe.top_k
    ex.engine_abort()
    ex.engine_enqueue(1, bound=0)
    ex.engine_round(["completions"])
    ex.engine_round(["completions"])
    assert reg.counter("moe.products").value - before[2] == \
        2 * (4 + 2 * 2) * 2
    assert reg.gauge("moe.experts_held").value == cfg.held_experts


def test_engine_abort_mid_decode_releases_everything():
    """An engine-mode run ending mid-decode must leak nothing: no parked
    pool state in the PartialRolloutCache, every slot free, no
    PinnedParams, no open ledger groups."""
    ex = _executor()
    ex.engine_configure(max_running_rows=8)
    ex.engine_enqueue(0, bound=0)
    ex.engine_round(["completions"])          # one round: rows mid-decode
    eng = ex._engine
    assert len(eng.cache) == 1 and eng.slots.free_count < 8
    dropped = ex.engine_abort()
    assert dropped == 4
    assert len(eng.cache) == 0
    assert eng.slots.free_count == 8 and not eng.tickets
    assert eng.ledger.open_groups == 0 and not eng.waiting
    assert ex.engine_inflight() == [] and ex.pinned_count() == 0


def test_engine_requires_chunking_and_supported_cache():
    ex = _executor(chunk=0)
    with pytest.raises(AssertionError, match="chunk"):
        RolloutEngine(ex)


# ------------------------------------------------------- group ledger -----

def _row(tokens=(2,), prompt_len=0):
    return {"tokens": np.asarray(tokens, np.int32), "logp": None,
            "version": 0, "prompt_len": prompt_len, "queue_wait_s": 0.0}


def _ticket(batch, group, sib):
    return RowJob(batch_index=batch, group=group, sib=sib,
                  prompt=None, answer="0")


def test_ledger_n_per_prompt_1_completes_on_first_row():
    led = GroupLedger(1)
    led.open_group(0, 0, "0")
    assert led.add(_ticket(0, 0, 0), _row())
    (g,) = led.pop_batch(0, 1)
    assert g["rewards"].shape == (1,) and g["advantages"].shape == (1,)
    # RLOO mean-baseline of a singleton group is identically zero
    np.testing.assert_allclose(g["advantages"], 0.0)


def test_ledger_siblings_complete_in_any_order_same_round():
    led = GroupLedger(3)
    led.open_group(0, 0, "0")
    assert not led.add(_ticket(0, 0, 2), _row())
    assert not led.add(_ticket(0, 0, 0), _row())
    assert led.add(_ticket(0, 0, 1), _row())
    (g,) = led.pop_batch(0, 1)
    assert sorted(g["rows"]) == [0, 1, 2]


def test_ledger_duplicate_sibling_raises():
    led = GroupLedger(2)
    led.open_group(0, 0, "0")
    led.add(_ticket(0, 0, 1), _row())
    with pytest.raises(AssertionError, match="duplicate"):
        led.add(_ticket(0, 0, 1), _row())


def test_ledger_invalidate_and_reopen_after_killed_worker():
    """A sibling dies with its worker mid-group: the batch's groups are
    invalidated (complete ones included -- the batch can no longer be
    assembled) and re-opened by re-admission, finishing cleanly."""
    led = GroupLedger(2)
    for g in range(2):
        led.open_group(0, g, "0")
    led.add(_ticket(0, 0, 0), _row())
    led.add(_ticket(0, 0, 1), _row())          # group 0 complete
    led.add(_ticket(0, 1, 0), _row())          # group 1 partial: lost row
    assert led.invalidate_batch(0) == 3
    assert led.open_groups == 0 and led.complete_groups == 0
    for g in range(2):                         # supervised re-admission
        led.open_group(0, g, "0")
    done = [led.add(_ticket(0, g, s), _row())
            for g in range(2) for s in range(2)]
    assert done == [False, True, False, True]
    assert len(led.pop_batch(0, 2)) == 2


@settings(max_examples=25, deadline=None)
@given(order=st.permutations(list(range(12))))
def test_ledger_no_drop_no_duplicate_across_finish_orders(order):
    """Property: whatever order 12 rows (2 batches x 3 groups x 2 sibs)
    finish in, every group completes exactly once and both batches pop
    with all their rows -- nothing dropped, nothing duplicated."""
    rows = [(b, g, s) for b in range(2) for g in range(3) for s in range(2)]
    led = GroupLedger(2)
    for b in range(2):
        for g in range(3):
            led.open_group(b, g, str(b * 3 + g))
    completed = []
    for i in order:
        b, g, s = rows[i]
        if led.add(_ticket(b, g, s), _row(tokens=(b * 100 + g * 10 + s, 2))):
            completed.append((b, g))
    assert sorted(completed) == sorted(
        (b, g) for b in range(2) for g in range(3))
    for b in range(2):
        groups = led.pop_batch(b, 3)
        got = sorted(tuple(gr["rows"][s]["tokens"][0] for s in range(2))
                     for gr in groups)
        assert got == [(b * 100 + g * 10, b * 100 + g * 10 + 1)
                       for g in range(3)]
    assert led.open_groups == 0 and led.complete_groups == 0


# ---------------------------------------------- scheduler teardown leaks --

def test_scheduler_clear_releases_pins_and_parked_states():
    ex = _executor()
    sched = RolloutScheduler(ex)
    for n in range(2):
        job, state = ex.begin_batch_pinned(n)
        sched.admit(job, state)
    assert ex.pinned_count() == 2 and len(sched.cache) == 2
    dropped = sched.clear()
    assert len(dropped) == 2
    assert ex.pinned_count() == 0 and len(sched.cache) == 0


def test_drain_abandoned_mid_iteration_releases_leftovers():
    """A consumer that early-exits a ``drain()`` between chunks used to
    leak the remaining jobs' parked states and executor-side pins."""
    ex = _executor()
    sched = RolloutScheduler(ex)
    for n in range(3):
        job, state = ex.begin_batch_pinned(n)
        sched.admit(job, state)
    g = sched.drain()
    next(g)                     # take one finished batch, abandon the rest
    g.close()
    assert ex.pinned_count() == 0 and len(sched.cache) == 0
    assert sched.pending() == 0


# ------------------------------------------------------ pool integration --

def build_engine_pool(n_gens=2, staleness=2, max_steps=8, transport=None,
                      chaos=None, supervise=False, max_inflight=3):
    cfg = micro_cfg()
    rew = RewardExecutor(n_per_prompt=2)
    trn = TrainerExecutor(cfg, lr=5e-2, seed=0)
    gens, chans = build_generator_pool(
        cfg, trn,
        lambda g: ArithmeticTasks(prompt_len=8, max_operand=4, ops="+",
                                  seed=100 + g),
        n_generators=n_gens, seed=100, n_prompts=2, n_per_prompt=2,
        max_new=4, temperature=1.0, chunk=2, transport=transport)
    chans += [CommunicationChannel("completions", gens[0], rew,
                                   CommType.GATHER),
              CommunicationChannel("completions_with_reward", rew, trn,
                                   CommType.SCATTER)]
    sup = Supervisor(chaos=chaos) if (supervise or chaos) else None
    ctl = ExecutorController(
        gens + [rew, trn], chans, max_steps=max_steps, mode="async",
        staleness=staleness, timeout=300.0, supervise=sup,
        pool=PoolConfig(engine=True, max_inflight=max_inflight))
    return ctl, gens


def test_engine_pool_trains_in_order_with_zero_row_violations():
    ctl, gens = build_engine_pool(n_gens=2, max_steps=8)
    hist = ctl.run()
    try:
        assert [h["step"] for h in hist] == list(range(8))
        assert max(ctl.staleness_hist) <= 2
        for gen in gens:
            st_ = gen.call("engine_stats")
            assert st_["staleness_violations"] == 0
            assert st_["waiting"] == 0 and st_["running"] == 0
            assert st_["batches_emitted"] == 4
            assert gen.call("pinned_count") == 0
    finally:
        for gen in gens:
            gen.close()


def test_engine_pool_kill_respawns_and_readmits_inflight(tmp_path):
    """Chaos-kill a proc-backed engine worker at a batch enqueue: the
    supervisor respawns it, replays weights, and the registered readmit
    hook rebuilds the engine and re-enqueues the dead worker's in-flight
    batches -- the run completes on schedule with zero per-row staleness
    violations."""
    chaos = FaultPlan.parse("kill:generator1@batch=3")
    ctl, gens = build_engine_pool(n_gens=2, max_steps=8, transport="proc",
                                  chaos=chaos)
    hist = ctl.run()
    try:
        assert chaos.unfired() == []
        sup = ctl.supervisor
        assert [e["actor"] for e in sup.events("respawned")] == \
            ["generator1"]
        assert [e["actor"] for e in sup.events("readmitted")] == \
            ["generator1"]
        assert [h["step"] for h in hist] == list(range(8))
        for gen in gens:
            st_ = gen.call("engine_stats")
            assert st_["staleness_violations"] == 0
            assert st_["waiting"] == 0 and st_["running"] == 0
    finally:
        for gen in gens:
            gen.close()
