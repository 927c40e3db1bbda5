"""Rollout engine: prefill + chunked decode with partial-rollout resume.

The paper (Sec. 4.2) mitigates stragglers with partial rollouts (after Kimi
k1.5): long generations are produced in fixed-size chunks; incomplete
sequences keep their KV cache + cursor in a ``RolloutState`` and resume next
iteration.  ``rollout_chunk`` is the resumable unit; ``generate`` is the
convenience full rollout.

Behavior logprobs mu(y_t | x, y_<t) -- under the *sampling* distribution,
including temperature -- travel with the sample, exactly as the paper
communicates them from generator to trainer (Sec. 6).
"""
from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.kernels import dispatch
from repro.models import prefill, decode_step
from repro.models.serve import segment_params
from repro.rl.data import EOS, PAD


class RolloutState(NamedTuple):
    tokens: jax.Array          # [B, total_len] prompt + generated (PAD after)
    behavior_logp: jax.Array   # [B, total_len] mu logprob per generated token
    cache: Any
    last_logits: jax.Array     # [B, V] logits predicting the next token
    done: jax.Array            # [B] bool
    prompt_len: int


# prompt_len is static shape metadata, not data: registering it as pytree
# aux keeps it a Python int through jit, so the first rollout_chunk call
# (fresh state, int leaf) and resumed calls (traced int32 leaf) no longer
# produce distinct jit signatures -- one compilation per (cfg, shape).
jax.tree_util.register_pytree_node(
    RolloutState,
    lambda s: ((s.tokens, s.behavior_logp, s.cache, s.last_logits, s.done),
               s.prompt_len),
    lambda aux, ch: RolloutState(*ch, prompt_len=aux),
)


@functools.partial(jax.jit,
                   static_argnames=("cfg", "total_len", "dtype",
                                    "cache_len"))
def start_rollout(params, cfg, prompts, total_len: int,
                  dtype=jnp.float32, extra=None,
                  cache_len: int = 0) -> RolloutState:
    """prompts: [B, S_p] int32 (rectangular).  ``cache_len`` overrides
    the ring size (the engine prefills donor rows one slot longer than
    ``total_len`` so finished rows can park on a spare slot).

    Jitted end-to-end: the eager ``models.prefill`` dispatches hundreds
    of small ops per call, which dominated the engine's per-row B=1
    admission prefills (and the pool's per-batch prefills) on CPU; one
    compiled call per (cfg, shape) amortizes that away."""
    B, Sp = prompts.shape
    batch = {"tokens": prompts}
    if extra:
        batch.update(extra)
    if not cache_len:
        cache_len = total_len + (cfg.frontend_tokens
                                 if cfg.family == "vlm" else 0)
    last_logits, cache = prefill(params, cfg, batch, cache_len=cache_len,
                                 dtype=dtype)
    tokens = jnp.zeros((B, total_len), jnp.int32).at[:, :Sp].set(prompts)
    return RolloutState(
        tokens=tokens,
        behavior_logp=jnp.zeros((B, total_len), jnp.float32),
        cache=cache,
        last_logits=last_logits,
        done=jnp.zeros((B,), bool),
        prompt_len=Sp,
    )


def _sample(logits, key, temperature: float):
    """Fused Gumbel-max draw + behavior logprob via the kernel-dispatch
    layer: one streamed pass over vocab tiles instead of a [B, V] fp32
    log-softmax per decode step."""
    return dispatch.sample(logits, key, temperature)


@functools.partial(jax.jit,
                   static_argnames=("cfg", "n_steps", "temperature"))
def rollout_chunk(params, cfg, state: RolloutState, key, *,
                  n_steps: int, temperature: float = 1.0) -> RolloutState:
    """Generate up to n_steps tokens; resumable (partial rollout)."""
    cursor = state.cache["pos"] - (cfg.frontend_tokens
                                   if cfg.family == "vlm" else 0)
    segments = segment_params(params, cfg)     # once, not once a step

    def body(carry, k):
        cache, logits, done = carry
        tok, lp = _sample(logits, k, temperature)
        tok = jnp.where(done, PAD, tok)
        # PAD emissions (done rows, or a live row drawing id 0) are never
        # action positions: keep mu consistent with the action mask
        lp = jnp.where(tok == PAD, 0.0, lp)
        new_done = done | (tok == EOS)
        new_logits, cache = decode_step(params, cfg, cache, tok[:, None],
                                        segments)
        return (cache, new_logits, new_done), (tok, lp)

    keys = jax.random.split(key, n_steps)
    (cache, last_logits, done), (toks, lps) = jax.lax.scan(
        body, (state.cache, state.last_logits, state.done), keys)
    toks = jnp.moveaxis(toks, 0, 1)      # [B, n_steps]
    lps = jnp.moveaxis(lps, 0, 1)
    tokens = jax.lax.dynamic_update_slice(state.tokens, toks, (0, cursor))
    blp = jax.lax.dynamic_update_slice(state.behavior_logp, lps, (0, cursor))
    return RolloutState(tokens=tokens, behavior_logp=blp, cache=cache,
                        last_logits=last_logits, done=done,
                        prompt_len=state.prompt_len)


def finalize_rollout(state: RolloutState, max_new: int) -> RolloutState:
    """Slice a bucket-padded rollout back to ``prompt + max_new`` tokens.

    At most ``chunk - 1`` overshoot decode steps land in the sliced-off
    tail; ``done`` is recomputed from the kept region so a row that only
    EOS'd in the overshoot still reads as unfinished.  A state already at
    its budget is returned unchanged.  Shared by ``generate`` and the
    chunk scheduler (``repro.rl.scheduler``), so the monolithic and
    chunk-scheduled paths emit bit-for-bit identical batches.
    """
    Sp = state.prompt_len
    if state.tokens.shape[1] == Sp + max_new:
        return state
    tokens = state.tokens[:, :Sp + max_new]
    return state._replace(
        tokens=tokens,
        behavior_logp=state.behavior_logp[:, :Sp + max_new],
        done=(tokens[:, Sp:] == EOS).any(axis=-1))


def generate(params, cfg, prompts, *, max_new: int, key,
             temperature: float = 1.0, chunk: int = 0,
             dtype=jnp.float32, extra=None) -> RolloutState:
    """Full rollout = start + ceil(max_new/chunk) resumable chunks.

    Every chunk runs with the same static ``n_steps == chunk`` so
    ``rollout_chunk`` compiles exactly once per (cfg, shape) -- a ragged
    final chunk used to change ``n_steps`` and retrace every call.  The
    token/logprob buffers are padded up to the bucketed length and sliced
    back to ``prompt + max_new`` by ``finalize_rollout``.  The returned
    state is terminal either way (its buffers are full); resume via
    ``rollout_chunk`` on a state sized for the full budget instead.
    """
    B, Sp = prompts.shape
    if max_new <= 0:
        return start_rollout(params, cfg, prompts, Sp, dtype=dtype,
                             extra=extra)
    chunk = chunk or max_new
    n_chunks = -(-max_new // chunk)
    padded = n_chunks * chunk
    state = start_rollout(params, cfg, prompts, Sp + padded, dtype=dtype,
                          extra=extra)
    for _ in range(n_chunks):
        key, sub = jax.random.split(key)
        state = rollout_chunk(params, cfg, state, sub, n_steps=chunk,
                              temperature=temperature)
    return finalize_rollout(state, max_new)


def action_mask(state: RolloutState) -> jax.Array:
    """1.0 on generated (non-PAD) positions after the prompt."""
    B, T = state.tokens.shape
    pos = jnp.arange(T)[None, :]
    gen = pos >= state.prompt_len
    return (gen & (state.tokens != PAD)).astype(jnp.float32)


# ------------------------------------------- continuous-batching slot pool -
#
# The engine (repro.rl.engine) decodes a pool of rows at *divergent*
# positions: ``cache["pos"]`` becomes a [R] vector of per-row cursors
# (see ``gqa_decode``'s per-row mode), rows are admitted into freed
# batch slots by grafting a B=1 prefill (``admit_row``), and finished
# rows keep ticking harmlessly -- their cursor clamps onto the ring's
# spare slot (``cache_len == total_len + 1``) until the slot is reused.

def start_row_pool(cfg, n_rows: int, total_len: int, prompt_len: int,
                   dtype=jnp.float32, *, kv_layout: str = "dense",
                   kv_page_size: int = 0, kv_pages: int = 0) -> RolloutState:
    """Empty slot-pool state: every row starts done (a free slot) with
    its decode cursor at 0.  No prefill runs here -- rows get real
    content only via ``admit_row`` (dense) / ``admit_row_paged``.

    ``kv_layout="paged"`` swaps the dense per-row ring for the paged
    arena: KV memory is ``kv_pages`` shared pages of ``kv_page_size``
    slots (defaults: page size 16; enough pages for every row, i.e. no
    admission backpressure) and each row owns a page table instead of a
    ring stripe, with all tables starting on the trash page."""
    from repro.models.serve import assert_engine_cache, init_cache
    layout = kv_layout or "dense"
    assert_engine_cache(cfg, layout)
    if layout == "paged":
        from repro.models.paging import paged_blocks
        page_size = int(kv_page_size) or 16
        mb = paged_blocks(total_len, page_size)
        n_pages = int(kv_pages) or n_rows * mb
        cache = init_cache(cfg, n_rows, total_len, dtype, layout="paged",
                           page_size=page_size, n_pages=n_pages)
    else:
        cache = init_cache(cfg, n_rows, total_len + 1, dtype)
    cache["pos"] = jnp.zeros((n_rows,), jnp.int32)
    return RolloutState(
        tokens=jnp.zeros((n_rows, total_len), jnp.int32),
        behavior_logp=jnp.zeros((n_rows, total_len), jnp.float32),
        cache=cache,
        last_logits=jnp.zeros((n_rows, cfg.vocab), jnp.float32),
        done=jnp.ones((n_rows,), bool),
        prompt_len=prompt_len,
    )


@jax.jit
def admit_row(state: RolloutState, row: RolloutState, slot) -> RolloutState:
    """Graft a freshly-prefilled single-row state (``start_rollout`` on
    a [1, Sp] prompt with ``cache_len = total_len + 1``) into pool row
    ``slot``.  ``slot`` is traced: admissions into different slots share
    one compilation."""
    from repro.models.serve import stitch_cache_row
    sl = jnp.asarray(slot)
    tokens = jax.lax.dynamic_update_slice(state.tokens, row.tokens, (sl, 0))
    blp = jax.lax.dynamic_update_slice(state.behavior_logp,
                                       row.behavior_logp, (sl, 0))
    logits = jax.lax.dynamic_update_slice(
        state.last_logits, row.last_logits.astype(state.last_logits.dtype),
        (sl, 0))
    return RolloutState(tokens=tokens, behavior_logp=blp,
                        cache=stitch_cache_row(state.cache, row.cache, sl),
                        last_logits=logits,
                        done=state.done.at[sl].set(False),
                        prompt_len=state.prompt_len)


@functools.partial(jax.jit, static_argnames=("cfg", "n_cached"))
def admit_row_paged(params, cfg, state: RolloutState, prompt, pages_row,
                    slot, *, n_cached: int) -> RolloutState:
    """Admit one prompt row into a *paged* pool: prefill only the
    suffix past the ``n_cached`` radix-cached prompt tokens, reading
    the cached prefix KVs straight out of the shared pages.

    prompt: [1, Sp] int32; pages_row: [max_blocks + 1] int32 physical
    pages for the row (last entry the trash page); ``n_cached`` is
    static (block-aligned, < Sp) so admissions with the same hit length
    share one compilation, and ``slot`` is traced like ``admit_row``'s.

    With ``n_cached == 0`` the extend path degenerates to a full
    prefill (empty prefix concat), so fresh admissions produce logits
    and KVs bit-for-bit equal to the dense ``start_rollout`` graft."""
    from repro.models import backbone as bb
    from repro.models.serve import _extend_collect, _moe_tally
    sl = jnp.asarray(slot)
    Sp = prompt.shape[1]
    T = state.tokens.shape[1]
    cache = state.cache
    P = cache["segments"][0]["k"].shape[2]
    ncb = n_cached // P
    assert n_cached == ncb * P and n_cached < Sp, (n_cached, P, Sp)
    prefix_kvs = []
    for seg in cache["segments"]:
        shape = (seg["k"].shape[0], 1, n_cached, cfg.n_kv_heads, cfg.hd)
        prefix_kvs.append((seg["k"][:, pages_row[:ncb]].reshape(shape),
                           seg["v"][:, pages_row[:ncb]].reshape(shape)))
    x = bb._embed(params, cfg, prompt[:, n_cached:])
    x, kv_segs, tally = _extend_collect(params, cfg, x, prefix_kvs, n_cached)
    last_logits = bb._logits(params, cfg, x[:, -1])

    pos_sfx = n_cached + jnp.arange(Sp - n_cached)
    pg = pages_row[pos_sfx // P]
    off = pos_sfx % P
    new_segs = []
    for seg, (ks, vs) in zip(cache["segments"], kv_segs):
        L = ks.shape[0]
        new_segs.append({
            "k": seg["k"].at[:, pg, off].set(
                ks[:, 0].reshape(L, Sp - n_cached, -1).astype(seg["k"].dtype)),
            "v": seg["v"].at[:, pg, off].set(
                vs[:, 0].reshape(L, Sp - n_cached, -1).astype(seg["v"].dtype)),
        })
    row_tokens = jnp.zeros((T,), jnp.int32).at[:Sp].set(prompt[0])
    new_cache = {
        "pos": cache["pos"].at[sl].set(Sp),
        "page_table": cache["page_table"].at[sl].set(
            pages_row.astype(jnp.int32)),
        "segments": new_segs,
    }
    if "moe" in cache:
        new_cache["moe"] = _moe_tally(cfg, cache, tally)
    return RolloutState(
        tokens=state.tokens.at[sl].set(row_tokens),
        behavior_logp=state.behavior_logp.at[sl].set(0.0),
        cache=new_cache,
        last_logits=state.last_logits.at[sl].set(
            last_logits[0].astype(state.last_logits.dtype)),
        done=state.done.at[sl].set(False),
        prompt_len=state.prompt_len)


@jax.jit
def release_row(state: RolloutState, slot) -> RolloutState:
    """Remap a harvested row's page table to the trash page so its
    zombie decode writes (the slot keeps ticking until readmitted) can
    never land in pages the allocator may have handed to another row."""
    pt = state.cache["page_table"]
    trash = state.cache["segments"][0]["k"].shape[1] - 1
    row = jnp.full((pt.shape[1],), trash, pt.dtype)
    new_cache = {**state.cache,
                 "page_table": pt.at[jnp.asarray(slot)].set(row)}
    return state._replace(cache=new_cache)


@functools.partial(jax.jit,
                   static_argnames=("cfg", "n_steps", "temperature"))
def rollout_rows_chunk(params, cfg, state: RolloutState, key, *,
                       n_steps: int, temperature: float = 1.0
                       ) -> RolloutState:
    """``rollout_chunk`` with per-row cursors: each row samples and
    writes at its own ``cache["pos"][r]``.  Done (or free) rows emit PAD
    and clamp their cursor at ``total_len`` -- the ring's spare slot --
    so their zombie KV writes never touch a live row's slots, and the
    token write at the out-of-range column drops.  Paged pools clamp at
    ``max_blocks * page_size`` instead: the block index then selects the
    table's trailing trash entry (same zombie-write guarantee, and the
    clamp is >= total_len so token writes still drop)."""
    B, T = state.tokens.shape
    rows = jnp.arange(B)
    if "page_table" in state.cache:
        clamp = (state.cache["page_table"].shape[1] - 1) \
            * state.cache["segments"][0]["k"].shape[2]
    else:
        clamp = T
    segments = segment_params(params, cfg)     # once, not once a step

    def body(carry, k):
        tokens, blp, cache, logits, done = carry
        tok, lp = _sample(logits, k, temperature)
        tok = jnp.where(done, PAD, tok)
        lp = jnp.where(tok == PAD, 0.0, lp)
        new_done = done | (tok == EOS)
        col = cache["pos"]                         # [B] per-row cursors
        tokens = tokens.at[rows, col].set(tok, mode="drop")
        blp = blp.at[rows, col].set(lp, mode="drop")
        new_logits, cache = decode_step(params, cfg, cache, tok[:, None],
                                        segments)
        cache = {**cache, "pos": jnp.minimum(cache["pos"], clamp)}
        return (tokens, blp, cache, new_logits, new_done), None

    keys = jax.random.split(key, n_steps)
    (tokens, blp, cache, last_logits, done), _ = jax.lax.scan(
        body, (state.tokens, state.behavior_logp, state.cache,
               state.last_logits, state.done), keys)
    return RolloutState(tokens=tokens, behavior_logp=blp, cache=cache,
                        last_logits=last_logits, done=done,
                        prompt_len=state.prompt_len)
