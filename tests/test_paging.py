"""Paged KV-cache subsystem (ISSUE 10): block allocator invariants,
radix prefix reuse, page-table decode parity, and engine integration.

The load-bearing check is bitwise parity: a paged pool whose logical
row length ``max_blocks * page_size`` equals the dense pool's
``total_len + 1`` must admit and decode bit-for-bit identically to the
dense ring -- gathers reorder memory, never math.  Masked columns score
``NEG_INF`` whose exp underflows to exact zero, so page-resident
garbage can never perturb a reduction.  On top of that: the allocator
can neither leak nor double-free, a dry arena is admission
backpressure (never a crash), and a radix hit admits a sibling from
shared pages with logits bitwise-equal to a fresh prefill.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from _hypothesis_compat import given, settings, st
from repro.kernels import dispatch
from repro.kernels import paged_attention as pa
from repro.kernels.paged_attention import (block_pages,
                                           paged_attention_kernel,
                                           paged_attention_ref)
from repro.models import init_params
from repro.models.paging import (PagePlan, PagePool, RadixCache,
                                 paged_blocks, paged_clamp, plan_admission,
                                 release_plan)
from repro.models.serve import assert_engine_cache
from repro.rl.rollout import (admit_row, admit_row_paged, release_row,
                              rollout_rows_chunk, start_rollout,
                              start_row_pool)

from test_genpool import micro_cfg


def _params(cfg):
    return init_params(cfg, jax.random.PRNGKey(0), jnp.float32)


# ------------------------------------------------------- block allocator --

def test_paged_blocks_and_clamp():
    assert paged_blocks(9, 5) == 2 and paged_blocks(10, 5) == 2
    assert paged_blocks(11, 5) == 3
    # the clamp always covers the sequence: a clamped cursor's block
    # index selects the table's trailing trash entry
    for total, p in [(9, 5), (16, 4), (7, 16)]:
        assert paged_clamp(total, p) >= total


def test_page_pool_alloc_free_roundtrip():
    pool = PagePool(4)
    pages = [pool.alloc() for _ in range(4)]
    assert sorted(pages) == [0, 1, 2, 3]
    assert pool.alloc() is None               # dry arena: None, no crash
    assert pool.trash_page == 4               # never handed out
    for p in pages:
        assert pool.decref(p)                 # last ref: page freed
    pool.assert_no_leaks()
    assert pool.free_count == 4


def test_page_pool_refcount_no_double_free():
    pool = PagePool(2)
    p = pool.alloc()
    pool.incref(p)
    assert not pool.decref(p)                 # one holder remains
    assert pool.decref(p)
    with pytest.raises(AssertionError, match="double free"):
        pool.decref(p)
    with pytest.raises(AssertionError, match="use-after-free"):
        pool.incref(p)


def test_page_pool_alloc_many_all_or_nothing():
    pool = PagePool(3)
    assert pool.alloc_many(4) is None         # would be a partial grab
    assert pool.pages_in_use == 0             # nothing was taken
    got = pool.alloc_many(3)
    assert len(got) == 3 and pool.free_count == 0
    for p in got:
        pool.decref(p)
    pool.assert_no_leaks()


# ------------------------------------------------------------ radix tree --

def test_radix_insert_match_acquire():
    pool = PagePool(8)
    radix = RadixCache(pool, page_size=4)
    prompt = tuple(range(12))
    pages = pool.alloc_many(3)
    assert radix.insert(prompt, pages) == 3
    assert len(radix) == 3
    # full match, block-truncated match, capped match, miss
    assert radix.match(prompt) == pages
    assert radix.match(prompt[:11]) == pages[:2]
    assert radix.match(prompt, max_tokens=11) == pages[:2]
    assert radix.match((99,) * 12) == []
    # acquire refs every matched page on top of the tree's ref
    got = radix.acquire(prompt)
    assert got == pages
    assert all(pool.refcount(p) == 3 for p in pages)  # alloc + tree + row
    for p in got + pages:
        pool.decref(p)                        # row hold + original alloc
    radix.clear()
    pool.assert_no_leaks()


def test_radix_insert_is_idempotent_first_writer_wins():
    pool = PagePool(8)
    radix = RadixCache(pool, page_size=4)
    prompt = tuple(range(8))
    a = pool.alloc_many(2)
    b = pool.alloc_many(2)
    assert radix.insert(prompt, a) == 2
    assert radix.insert(prompt, b) == 0       # same blocks: nothing new
    assert radix.match(prompt) == a           # first writer's pages stay
    for p in a + b:
        pool.decref(p)
    radix.clear()
    pool.assert_no_leaks()


def test_radix_evicts_lru_leaves_and_keeps_referenced_pages():
    pool = PagePool(6)
    radix = RadixCache(pool, page_size=2)
    cold = (1, 2, 3, 4)                       # 2 blocks, shared first block
    hot = (1, 2, 9, 9)
    pc = pool.alloc_many(2)
    ph = [pool.alloc()]
    radix.insert(cold, pc)
    radix.insert(hot, pc[:1] + ph)            # shares the (1, 2) node
    for p in pc + ph:
        pool.decref(p)                        # tree is now the only holder
    hold = radix.acquire(hot)                 # a live row pins hot's pages
    radix.match(hot)                          # and touches them (LRU)
    assert radix.evict(10) == 1               # only cold's leaf is free
    assert radix.match(cold) == pc[:1]        # interior prefix survives
    assert radix.match(hot) == pc[:1] + ph    # pinned path untouched
    for p in hold:
        pool.decref(p)
    assert radix.evict(10) == 2               # leaf, then exposed parent
    assert len(radix) == 0
    pool.assert_no_leaks()


# -------------------------------------------------------- admission plan --

def test_plan_admission_fresh_then_radix_hit():
    pool = PagePool(8)
    radix = RadixCache(pool, page_size=4)
    prompt = tuple(range(13))                 # 3 full blocks + 1 token
    p1 = plan_admission(pool, radix, prompt, max_blocks=4, page_size=4)
    assert p1.n_cached == 0 and len(p1.table) == 4
    radix.insert(prompt, p1.table)
    p2 = plan_admission(pool, radix, prompt, max_blocks=4, page_size=4)
    assert p2.n_cached == 12                  # all 3 full blocks reused
    assert p2.table[:3] == p1.table[:3]
    assert pool.pages_in_use == 5             # 4 + 1 fresh, not 8
    release_plan(pool, p1)
    release_plan(pool, p2)
    radix.clear()
    pool.assert_no_leaks()


def test_plan_admission_caps_cached_below_prompt():
    """A fully block-aligned prompt must still recompute its last block:
    admission needs last-token logits, so n_cached < len(prompt)."""
    pool = PagePool(8)
    radix = RadixCache(pool, page_size=4)
    prompt = tuple(range(8))                  # exactly 2 blocks
    p1 = plan_admission(pool, radix, prompt, max_blocks=2, page_size=4)
    radix.insert(prompt, p1.table)
    p2 = plan_admission(pool, radix, prompt, max_blocks=2, page_size=4)
    assert p2.n_cached == 4 < len(prompt)
    release_plan(pool, p1)
    release_plan(pool, p2)
    radix.clear()
    pool.assert_no_leaks()


def test_plan_admission_backpressure_rolls_back_refs():
    pool = PagePool(3)
    radix = RadixCache(pool, page_size=4)
    prompt = tuple(range(13))
    held = pool.alloc_many(2)                 # live rows pin 2 of 3 pages
    assert plan_admission(pool, radix, prompt, 4, 4) is None
    assert pool.pages_in_use == 2             # the failed plan took nothing
    for p in held:
        pool.decref(p)
    pool.assert_no_leaks()


def test_plan_admission_evicts_cold_prefixes_under_pressure():
    pool = PagePool(4)
    radix = RadixCache(pool, page_size=4)
    cold = tuple(range(13))
    p1 = plan_admission(pool, radix, cold, 4, 4)
    radix.insert(cold, p1.table)
    release_plan(pool, p1)                    # only the tree holds them now
    assert pool.free_count == 1               # the partial 4th block freed
    p2 = plan_admission(pool, radix, tuple(range(100, 113)), 4, 4)
    assert p2 is not None                     # cold prefix was evicted
    release_plan(pool, p2)
    radix.clear()
    pool.assert_no_leaks()


# ------------------------------------------------- cache family contract --

def _windowed_cfg():
    """llama4-style iRoPE micro config: alternating windowed/global."""
    from repro.configs.base import MoEConfig
    from repro.configs.llama4_scout_17b_a16e import smoke
    return smoke().replace(
        n_layers=2, d_model=32, n_heads=2, n_kv_heads=2, head_dim=16,
        d_ff=64, vocab=64, window=4, window_pattern=2,
        moe=MoEConfig(n_experts=2, top_k=1, n_shared=1, d_expert=64,
                      router="sigmoid")).validate()


def test_engine_cache_contract_paged_vs_dense():
    cfg = _windowed_cfg()
    assert_engine_cache(cfg, "paged")         # page tables admit windows
    with pytest.raises(AssertionError, match="paged layout"):
        assert_engine_cache(cfg, "dense")     # a windowed ring wraps
    for layout in ("dense", "paged"):         # still rejected everywhere
        with pytest.raises(AssertionError, match="latent"):
            assert_engine_cache(micro_cfg().replace(attn_kind="mla"), layout)
        with pytest.raises(AssertionError, match="family"):
            assert_engine_cache(micro_cfg().replace(family="hybrid"), layout)


# -------------------------------------------------- paged decode parity --

def _pools(cfg, R, T, Sp, P):
    """Matched dense + paged pools: paged logical length mb*P equals the
    dense ring's total_len + 1, the bitwise-parity precondition."""
    mb = paged_blocks(T, P)
    assert mb * P == T + 1, (T, P)
    dense = start_row_pool(cfg, R, T, Sp)
    paged = start_row_pool(cfg, R, T, Sp, kv_layout="paged", kv_page_size=P)
    return dense, paged, mb


def _admit_pair(params, cfg, dense, paged, pr, slot, pool, radix, mb, P):
    row = start_rollout(params, cfg, pr, dense.tokens.shape[1],
                        cache_len=dense.tokens.shape[1] + 1)
    dense = admit_row(dense, row, slot)
    plan = plan_admission(pool, radix, tuple(int(t) for t in pr[0]), mb, P)
    if plan is None:
        return dense, paged, None
    paged = admit_row_paged(
        params, cfg, paged, pr,
        jnp.asarray(plan.table + (pool.trash_page,), jnp.int32),
        slot, n_cached=plan.n_cached)
    if radix is not None:
        radix.insert(tuple(int(t) for t in pr[0]), plan.table)
    return dense, paged, plan


def test_paged_decode_matches_dense_bitwise():
    cfg = micro_cfg()
    params = _params(cfg)
    T, Sp, P = 9, 5, 5
    dense, paged, mb = _pools(cfg, 3, T, Sp, P)
    pool = PagePool(3 * mb)
    prompts = [jnp.asarray([[1, 5, 6, 7, 2]], jnp.int32),
               jnp.asarray([[1, 8, 9, 4, 3]], jnp.int32)]
    for slot, pr in enumerate(prompts):
        dense, paged, _ = _admit_pair(params, cfg, dense, paged, pr, slot,
                                      pool, None, mb, P)
    np.testing.assert_array_equal(np.asarray(dense.last_logits),
                                  np.asarray(paged.last_logits))
    key = jax.random.PRNGKey(7)
    dense = rollout_rows_chunk(params, cfg, dense, key, n_steps=4)
    paged = rollout_rows_chunk(params, cfg, paged, key, n_steps=4)
    np.testing.assert_array_equal(np.asarray(dense.tokens),
                                  np.asarray(paged.tokens))
    np.testing.assert_array_equal(np.asarray(dense.last_logits),
                                  np.asarray(paged.last_logits))


def test_radix_hit_admission_matches_fresh_prefill_bitwise():
    """A sibling admitted from shared radix pages (only the suffix
    prefilled) must produce the same last-token logits as the full
    prefill that populated those pages."""
    cfg = micro_cfg()
    params = _params(cfg)
    T, P = 19, 5                              # mb = 4
    paged = start_row_pool(cfg, 3, T, 12, kv_layout="paged", kv_page_size=P)
    pool = PagePool(12)
    radix = RadixCache(pool, P)
    pr = jnp.asarray([list(range(1, 13))], jnp.int32)
    prompt = tuple(int(t) for t in pr[0])
    p1 = plan_admission(pool, radix, prompt, 4, P)
    assert p1.n_cached == 0
    paged = admit_row_paged(
        params, cfg, paged, pr,
        jnp.asarray(p1.table + (pool.trash_page,), jnp.int32), 0, n_cached=0)
    radix.insert(prompt, p1.table)
    p2 = plan_admission(pool, radix, prompt, 4, P)
    assert p2.n_cached == 10                  # 2 full blocks reused
    paged = admit_row_paged(
        params, cfg, paged, pr,
        jnp.asarray(p2.table + (pool.trash_page,), jnp.int32), 1,
        n_cached=p2.n_cached)
    logits = np.asarray(paged.last_logits)
    np.testing.assert_array_equal(logits[0], logits[1])


def _run_mirrored(cfg, params, order, n_prompts=4):
    """Drive matched dense/paged pools through an interleaved
    admit/decode/release schedule given by ``order`` and return both.

    Releases are paged-only state transitions (the dense ring has no
    allocator); parity still requires released pages reallocated to new
    rows to decode identically, which is exactly what this exercises.
    """
    T, Sp, P, R = 9, 5, 5, 3
    dense, paged, mb = _pools(cfg, R, T, Sp, P)
    pool = PagePool(R * mb + 2)
    radix = RadixCache(pool, P)
    rng = np.random.RandomState(3)
    prompts = [jnp.asarray(rng.randint(1, cfg.vocab, (1, Sp)), jnp.int32)
               for _ in range(n_prompts)]
    live, plans, nxt = {}, {}, 0
    for step, op in enumerate(order):
        if op == 0 and nxt < len(prompts) and len(live) < R:
            slot = min(set(range(R)) - set(live))
            pr = prompts[nxt]
            dense, paged, plan = _admit_pair(params, cfg, dense, paged, pr,
                                             slot, pool, radix, mb, P)
            if plan is None:
                continue
            live[slot] = nxt
            plans[slot] = plan
            nxt += 1
        elif op == 1:
            key = jax.random.PRNGKey(step)
            dense = rollout_rows_chunk(params, cfg, dense, key, n_steps=2)
            paged = rollout_rows_chunk(params, cfg, paged, key, n_steps=2)
        elif op == 2 and live:
            slot = min(live)
            release_plan(pool, plans.pop(slot))
            paged = release_row(paged, slot)
            paged = paged._replace(done=paged.done.at[slot].set(True))
            dense = dense._replace(done=dense.done.at[slot].set(True))
            del live[slot]
    return dense, paged


def _assert_pools_equal(dense, paged):
    np.testing.assert_array_equal(np.asarray(dense.tokens),
                                  np.asarray(paged.tokens))
    np.testing.assert_array_equal(np.asarray(dense.behavior_logp),
                                  np.asarray(paged.behavior_logp))
    # logits parity is only claimed where logits are ever *used*: live
    # rows whose cursor is still in-bounds.  Released rows chew on the
    # ring's spare slot (dense) vs the trash page (paged), and a row at
    # the clamp keeps overwriting the spare slot dense-side while paged
    # writes land in trash -- in both cases the next sampled token would
    # drop, so the engine never consumes those logits
    T = dense.tokens.shape[1]
    lv = ~np.asarray(dense.done) & (np.asarray(dense.cache["pos"]) < T)
    np.testing.assert_array_equal(np.asarray(dense.last_logits)[lv],
                                  np.asarray(paged.last_logits)[lv])


def test_paged_matches_dense_across_admit_release_orders():
    cfg = micro_cfg()
    params = _params(cfg)
    rng = np.random.RandomState(0)
    for trial in range(3):
        order = rng.randint(0, 3, 12).tolist()
        dense, paged = _run_mirrored(cfg, params, order)
        _assert_pools_equal(dense, paged)


@settings(max_examples=10, deadline=None)
@given(order=st.lists(st.integers(min_value=0, max_value=2),
                      min_size=4, max_size=12))
def test_paged_matches_dense_property(order):
    """Property: any interleaving of admissions, decode chunks, and
    releases keeps paged decode bitwise equal to the dense ring."""
    cfg = micro_cfg()
    dense, paged = _run_mirrored(cfg, _params(cfg), order)
    _assert_pools_equal(dense, paged)


# ------------------------------------------------------- pallas kernel ---

def _arena(pos, K=2, g=2, hd=16, P=5, mb=4, n_pages=16):
    """A decode problem over an arena in its stored layout
    ``[n_pages + 1, P, K * hd]``, one row per cursor in ``pos``."""
    B = len(pos)
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(k1, (B, K * g, hd), jnp.float32)
    ak = jax.random.normal(k2, (n_pages + 1, P, K * hd), jnp.float32)
    av = jax.random.normal(k3, (n_pages + 1, P, K * hd), jnp.float32)
    pt = jnp.asarray(np.random.RandomState(0).randint(
        0, n_pages, (B, mb + 1)), jnp.int32)
    return q, ak, av, pt, jnp.asarray(pos, jnp.int32)


@pytest.fixture
def arena_problem():
    return _arena([3, 11, 19])


MB, PPB = 7, 3     # rows of 7 pages, DMA blocks of 3 (the last one ragged)


@pytest.mark.parametrize("window", [0, 6, MB * 16 + 10])
@pytest.mark.parametrize("P", [5, 16])
@pytest.mark.parametrize("K,g", [(2, 12), (8, 8), (8, 5), (1, 4)])
def test_paged_attention_kernel_matches_ref(monkeypatch, K, g, P, window):
    """Rows of every trip count in one call: cursors at 0, either side of
    a page edge and of a block edge, a row starting past its first block
    under the window, the last column, and the clamp ``max_blocks * P``
    (every column)."""
    hd = 16
    monkeypatch.setattr(pa, "_BLOCK_VMEM_BYTES", 4 * PPB * P * K * hd * 4)
    assert block_pages(P, K, hd, MB) == PPB
    pos = [0, P - 1, P, PPB * P - 1, PPB * P, 2 * PPB * P + 2,
           MB * P - 1, MB * P]
    q, ak, av, pt, pos = _arena(pos, K, g, hd, P, MB, n_pages=40)
    ref = paged_attention_ref(q, ak, av, pt, pos, window=window)
    ker = paged_attention_kernel(q, ak, av, pt, pos, window=window,
                                 interpret=True)
    np.testing.assert_allclose(np.asarray(ker), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("P,K,hd,mb,ppb", [
    (16, 2, 128, 33, 16),     # StarCoder2-3B: 16 KB pages
    (16, 8, 128, 33, 4),      # 8 kv heads: 64 KB pages
    (16, 2, 128, 10, 10),     # a short row: one block
    (64, 8, 256, 33, 1),      # a page over the budget still streams
])
def test_block_pages_from_shapes(P, K, hd, mb, ppb):
    assert block_pages(P, K, hd, mb) == ppb


def test_paged_attention_kernel_pos_zero_edge(arena_problem):
    """pos=0 leaves entire KV tiles fully masked; the online-softmax
    guard must zero them instead of propagating exp(NEG_INF - NEG_INF)."""
    q, ak, av, pt, _ = arena_problem
    pos = jnp.zeros((q.shape[0],), jnp.int32)
    ref = paged_attention_ref(q, ak, av, pt, pos)
    ker = paged_attention_kernel(q, ak, av, pt, pos, interpret=True)
    assert np.isfinite(np.asarray(ker)).all()
    np.testing.assert_allclose(np.asarray(ker), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_paged_attention_dispatch_routes(arena_problem, monkeypatch):
    q, ak, av, pt, pos = arena_problem
    monkeypatch.setenv("REPRO_KERNEL_MODE", "ref")
    ref = dispatch.paged_attention(q, ak, av, pt, pos)
    np.testing.assert_array_equal(
        np.asarray(ref), np.asarray(paged_attention_ref(q, ak, av, pt, pos)))
    monkeypatch.setenv("REPRO_KERNEL_MODE", "interpret")
    ker = dispatch.paged_attention(q, ak, av, pt, pos)
    np.testing.assert_allclose(np.asarray(ker), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    # the kernel route records its block: pages 5 x 2 heads x 16 -> all 4
    assert dispatch.paged_block_pages()[(5, 2, 16, 4)] == 4
    # an arena held as [pages, P, K, hd] is the same arena
    ker4 = dispatch.paged_attention(q, ak.reshape(17, 5, 2, 16),
                                    av.reshape(17, 5, 2, 16), pt, pos)
    np.testing.assert_array_equal(np.asarray(ker4), np.asarray(ker))


def test_paged_pool_decode_under_interpret_kernel(monkeypatch):
    """The whole serve path (scatter + kernel + residual stream) on the
    Pallas interpret route against the jnp route."""
    cfg = micro_cfg()
    params = _params(cfg)
    T, Sp, P = 9, 5, 5
    paged = start_row_pool(cfg, 2, T, Sp, kv_layout="paged", kv_page_size=P)
    pool = PagePool(4)
    plan = plan_admission(pool, None, tuple(range(1, 6)), 2, P)
    pr = jnp.asarray([[1, 2, 3, 4, 5]], jnp.int32)
    paged = admit_row_paged(
        params, cfg, paged, pr,
        jnp.asarray(plan.table + (pool.trash_page,), jnp.int32), 0,
        n_cached=0)
    key = jax.random.PRNGKey(5)
    monkeypatch.setenv("REPRO_KERNEL_MODE", "ref")
    a = rollout_rows_chunk(params, cfg, paged, key, n_steps=3)
    monkeypatch.setenv("REPRO_KERNEL_MODE", "interpret")
    monkeypatch.setenv("REPRO_KERNEL_MIN_SEQ", "1")
    b = rollout_rows_chunk(params, cfg, paged, key, n_steps=3)
    np.testing.assert_allclose(np.asarray(a.last_logits),
                               np.asarray(b.last_logits),
                               rtol=2e-4, atol=2e-4)


# --------------------------------------------------- engine integration --

def _paged_executor(**kw):
    from test_engine import _executor
    ex = _executor()
    ex.engine_configure(max_running_rows=8, kv_layout="paged",
                        kv_page_size=4, **kw)
    return ex


def _drain(ex, n_items, max_rounds=60):
    items, rounds = [], 0
    while len(items) < n_items and rounds < max_rounds:
        items += ex.engine_round(["completions"])
        rounds += 1
    return items


def test_engine_paged_exact_mu_and_prefix_reuse():
    """The engine's load-bearing correctness check under the paged
    layout: emitted mu must match a teacher-forced recompute, siblings
    must hit the radix, and abort must leave zero pages in use."""
    from repro.core.aipo import token_logprobs
    from repro.models import forward_train
    ex = _paged_executor()
    ex.engine_enqueue(0, bound=1)
    ex.engine_enqueue(1, bound=1)
    items = _drain(ex, 2)
    assert [it["batch_index"] for it in items] == [0, 1]
    st_ = ex.engine_stats()
    assert st_["kv_layout"] == "paged"
    assert st_["staleness_violations"] == 0
    assert st_["rows_harvested"] == 8
    assert st_["radix_hits"] > 0              # n_per_prompt=2 siblings
    assert st_["prefix_tokens_reused"] > 0
    assert 0.0 < st_["radix_hit_rate"] <= 1.0

    out = items[0]["snapshot"]["completions"]
    toks = np.asarray(out["tokens"])
    blp = np.asarray(out["behavior_logp"])
    mask = np.asarray(out["mask"])
    logits, _ = forward_train(ex.params, ex.cfg,
                              {"tokens": jnp.asarray(toks)})
    lp = np.asarray(token_logprobs(logits[:, :-1],
                                   jnp.asarray(toks[:, 1:])))
    rec = np.zeros_like(blp)
    rec[:, 1:] = lp
    np.testing.assert_allclose(blp * mask, rec * mask, atol=1e-4)

    ex.engine_abort()
    assert ex.engine_stats()["pages_in_use"] == 0   # radix cleared too


def test_engine_paged_kernel_sets_block_pages_gauge(monkeypatch):
    """A decode chunk that stages the paged kernel sets the
    ``kernels.paged_attention.block_pages`` gauge to the block the kernel
    streams at the engine's pool shape."""
    from repro.obs import metrics as obs_metrics
    gauge = obs_metrics.registry().gauge("kernels.paged_attention.block_pages")
    gauge.set(-1)
    monkeypatch.setenv("REPRO_KERNEL_MODE", "interpret")
    monkeypatch.setenv("REPRO_KERNEL_MIN_SEQ", "1")
    # 4-slot pages of 2 heads x 16: two pages a block, under the row's 3
    monkeypatch.setattr(pa, "_BLOCK_VMEM_BYTES", 4 * 2 * 4 * 2 * 16 * 4)
    jax.clear_caches()          # stage the chunk afresh under this route
    ex = _paged_executor()
    ex.engine_enqueue(0, bound=1)
    assert len(_drain(ex, 1)) == 1
    engine = ex._engine
    assert engine._max_blocks == 3
    assert gauge.value == 2
    assert dispatch.paged_block_pages()[(4, 2, 16, 3)] == 2
    jax.clear_caches()


def test_engine_paged_tiny_arena_backpressures_and_completes():
    """An arena sized for ~1.5 concurrent rows forces admissions to wait
    for harvests: the run must still complete every row, with the dry
    arena surfacing as backpressure stats -- never an OOM or a crash."""
    ex = _paged_executor(kv_pages=5)          # 3 blocks/row (prompt 8 + 4)
    ex.engine_enqueue(0, bound=2)
    items = _drain(ex, 1, max_rounds=120)
    assert len(items) == 1
    st_ = ex.engine_stats()
    assert st_["rows_harvested"] == 4
    assert st_["admission_backpressure"] > 0
    assert st_["waiting"] == 0 and st_["running"] == 0
    ex.engine_abort()
    assert ex.engine_stats()["pages_in_use"] == 0


def test_engine_paged_windowed_family_exact_mu():
    """iRoPE-style windowed layers -- which the dense engine layout
    rejects outright -- decode correctly from pages: mu matches the
    teacher-forced recompute that applies the same window masks."""
    from repro.core.aipo import token_logprobs
    from repro.core.executor import GeneratorExecutor
    from repro.models import forward_train
    from repro.rl.data import ArithmeticTasks
    cfg = _windowed_cfg()
    ex = GeneratorExecutor(
        cfg, ArithmeticTasks(prompt_len=8, max_operand=9, ops="+", seed=0),
        n_prompts=2, n_per_prompt=2, max_new=4, chunk=2, seed=0)
    ex.set_weights(_params(cfg), version=0)
    ex.engine_configure(max_running_rows=4, kv_layout="paged",
                        kv_page_size=4)
    ex.engine_enqueue(0, bound=1)
    items = _drain(ex, 1)
    out = items[0]["snapshot"]["completions"]
    toks = np.asarray(out["tokens"])
    blp = np.asarray(out["behavior_logp"])
    mask = np.asarray(out["mask"])
    logits, _ = forward_train(ex.params, ex.cfg,
                              {"tokens": jnp.asarray(toks)})
    lp = np.asarray(token_logprobs(logits[:, :-1],
                                   jnp.asarray(toks[:, 1:])))
    rec = np.zeros_like(blp)
    rec[:, 1:] = lp
    np.testing.assert_allclose(blp * mask, rec * mask, atol=1e-4)
