"""Each attention segment's weights are taken out of their layer stack once
per decode chunk (``serve.segment_params``), before the chunk's step loop,
not in every decode step.  The chunk programs must compute exactly what
slicing in each step computes: the same tokens, behaviour log-probs,
logits and cache, bit for bit."""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import configs
from repro.models import init_params
from repro.models.paging import paged_blocks
from repro.models.serve import segment_params, segment_slices
from repro.rl import rollout
from repro.rl.rollout import (admit_row_paged, rollout_chunk,
                              rollout_rows_chunk, start_rollout,
                              start_row_pool)

from test_genpool import micro_cfg


def _segmented_cfg():
    """Mellum2's layer pattern at CPU widths: three sliding layers (a
    window of 4, which the rows pass) then a full one with YaRN, in one
    expert stack holding 2 of 4 experts."""
    return configs.get_smoke("mellum2-12b-a2.5b").replace(
        n_layers=4, window_pattern=4, window=4, d_model=64, n_heads=4,
        n_kv_heads=2, head_dim=16, d_ff=32, vocab=128, experts_held=2)


def _params(cfg):
    return init_params(cfg, jax.random.PRNGKey(0), jnp.float32)


def _per_step(chunk, monkeypatch):
    """``chunk`` traced again with no segments taken before its loop, so
    that ``decode_step`` slices them out of the stacks in every step (a
    new function: JAX keeps one trace per function)."""
    monkeypatch.setattr(rollout, "segment_params", lambda params, cfg: None)

    @functools.partial(jax.jit, static_argnames=("cfg", "n_steps"))
    def per_step(params, cfg, state, key, *, n_steps):
        return chunk.__wrapped__(params, cfg, state, key, n_steps=n_steps)
    return per_step


def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        for u in (v if isinstance(v, (tuple, list)) else (v,)):
            if hasattr(u, "eqns"):
                yield u
            elif hasattr(getattr(u, "jaxpr", None), "eqns"):
                yield u.jaxpr


def _slices(jaxpr):
    """``slice`` equations anywhere in ``jaxpr``."""
    return sum((e.primitive.name == "slice")
               + sum(_slices(j) for j in _sub_jaxprs(e))
               for e in jaxpr.eqns)


def _step_loop_slices(jaxpr):
    """``slice`` equations inside the first (outermost) ``scan``."""
    for e in jaxpr.eqns:
        if e.primitive.name == "scan":
            return _slices(e.params["jaxpr"].jaxpr)
        for j in _sub_jaxprs(e):
            n = _step_loop_slices(j)
            if n is not None:
                return n
    return None


def _assert_same(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _dense_case(cfg, params):
    prompts = jnp.asarray([[1, 5, 6, 7, 2, 9], [1, 8, 9, 4, 3, 11]],
                          jnp.int32)
    return rollout_chunk, start_rollout(params, cfg, prompts, 6 + 8)


def _paged_case(cfg, params):
    """A pool of 3 slots with two rows admitted; each row owns its own
    pages, the last table entry the trash page."""
    T, Sp, P = 15, 6, 4
    state = start_row_pool(cfg, 3, T, Sp, kv_layout="paged", kv_page_size=P)
    mb = paged_blocks(T, P)
    for slot, prompt in enumerate(([1, 5, 6, 7, 2, 9], [1, 8, 9, 4, 3, 11])):
        pages = jnp.asarray(list(range(slot * mb, (slot + 1) * mb))
                            + [3 * mb], jnp.int32)
        state = admit_row_paged(params, cfg, state,
                                jnp.asarray([prompt], jnp.int32), pages,
                                slot, n_cached=0)
    return rollout_rows_chunk, state


@pytest.mark.parametrize("case", [_dense_case, _paged_case],
                         ids=["dense", "paged"])
def test_chunk_with_segments_taken_once_matches_per_step(case, monkeypatch):
    cfg = _segmented_cfg()
    assert segment_slices(cfg) == 2
    params = _params(cfg)
    chunk, state = case(cfg, params)
    key = jax.random.PRNGKey(3)
    loop = lambda fn: _step_loop_slices(jax.make_jaxpr(
        lambda p, s, k: fn(p, cfg, s, k, n_steps=8))(params, state,
                                                      key).jaxpr)
    once = chunk(params, cfg, state, key, n_steps=8)
    in_loop = loop(chunk)
    per_step = _per_step(chunk, monkeypatch)
    ref = per_step(params, cfg, state, key, n_steps=8)
    # the reference slices the two segments of every leaf in each step
    # of its loop, the chunk program in none
    leaves = len(jax.tree.leaves(params["moe_layers"]))
    assert loop(per_step) - in_loop == 2 * leaves
    assert int((once.tokens[:, 6:] != 0).sum()) > 0
    _assert_same(once.tokens, ref.tokens)
    _assert_same(once.behavior_logp, ref.behavior_logp)
    _assert_same(once.last_logits, ref.last_logits)
    _assert_same(once.done, ref.done)
    _assert_same(once.cache, ref.cache)


@pytest.mark.parametrize("make_cfg,n_slices",
                         [(micro_cfg, 0), (_segmented_cfg, 2)],
                         ids=["one-segment", "sliding-and-full"])
def test_segment_params_slices_only_proper_sub_ranges(make_cfg, n_slices):
    """A segment that spans its whole stack is the stack itself, so a
    one-segment model's chunk takes no slice at all; a model of several
    segments takes one slice of each leaf per proper sub-range."""
    cfg = make_cfg()
    params = _params(cfg)
    key = "moe_layers" if cfg.family == "moe" else "layers"
    jaxpr = jax.make_jaxpr(
        lambda p: [lp for lp, _, _ in segment_params(p, cfg)])(params).jaxpr
    assert segment_slices(cfg) == n_slices
    assert _slices(jaxpr) == n_slices * len(jax.tree.leaves(params[key]))
    if not n_slices:
        (seg,) = segment_params(params, cfg)
        assert all(a is b for a, b in zip(jax.tree.leaves(seg[0]),
                                          jax.tree.leaves(params[key])))
