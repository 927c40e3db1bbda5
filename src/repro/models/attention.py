"""Attention variants: GQA (+RoPE / M-RoPE), sliding-window, and MLA.

Training/prefill attention is *chunked over query blocks* (a pure-JAX flash
pattern): live score buffers are [B, K, g, block_q, Sk] instead of
[B, H, S, S], which is what makes 32k prefill lowerable.  Sliding-window
attention slices K/V to a fixed [window + block_q] span per query block, so
its compute is O(S * W), genuinely sub-quadratic.

Full-sequence call sites (GQA/MLA train + prefill) go through
``repro.kernels.dispatch.attention``: dense-causal self-attention segments
can route to the Pallas flash kernel (explicit VMEM tiling for the TPU
target), while windowed / cross / MLA-asymmetric segments and the
512-device dry-run fall back to ``chunked_attention`` below, the
lowering-safe reference path.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import dispatch
from repro.models.common import apply_rope, apply_mrope, dense_init, split_keys
from repro.models.sharding import constrain_attn

NEG_INF = -1e30


def _block_attend(q, k, v, row_pos, col_pos, *, causal, window):
    """q: [B, bq, K, g, hd]; k/v: [B, Sk, K, hd]; positions are absolute."""
    scale = q.shape[-1] ** -0.5
    scores = jnp.einsum("bqkgh,bskh->bkgqs", q, k,
                        preferred_element_type=jnp.float32) * scale
    mask = jnp.ones((row_pos.shape[0], col_pos.shape[0]), dtype=bool)
    if causal:
        mask &= col_pos[None, :] <= row_pos[:, None]
    if window:
        mask &= col_pos[None, :] > row_pos[:, None] - window
    mask &= (col_pos >= 0)[None, :]
    scores = jnp.where(mask[None, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bskh->bqkgh", probs.astype(v.dtype), v)
    return out


def chunked_attention(q, k, v, *, causal: bool = True, window: int = 0,
                      block_q: int = 512, q_offset: int = 0,
                      kv_positions: Optional[jax.Array] = None,
                      unroll: bool = False):
    """q: [B, Sq, H, hd], k/v: [B, Sk, K, hd] -> [B, Sq, H, hd].

    ``q_offset``: absolute position of q[0] (prefill continuation).
    ``kv_positions``: absolute position per KV slot (defaults to arange).
    """
    B, Sq, H, hd = q.shape
    Sk, K = k.shape[1], k.shape[2]
    g = H // K
    q = q.reshape(B, Sq, K, g, hd)
    if kv_positions is None:
        kv_positions = jnp.arange(Sk)

    if unroll:
        # cap the q-block count at 32 so full unrolling stays compilable;
        # cost_analysis then counts the whole attention (scan bodies are
        # otherwise counted once).
        block_q = max(block_q, -(-Sq // 32))
    block_q = min(block_q, Sq)
    pad = (-Sq) % block_q
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0), (0, 0)))
    n_blk = q.shape[1] // block_q
    qb = q.reshape(B, n_blk, block_q, K, g, hd)
    qb = jnp.moveaxis(qb, 1, 0)                      # [n_blk, B, bq, K, g, hd]

    use_window_slice = window and Sk > (window + block_q)
    span = window + block_q if use_window_slice else Sk

    def body(_, inputs):
        blk_idx, qi = inputs
        qs = blk_idx * block_q
        row_pos = q_offset + qs + jnp.arange(block_q)
        if use_window_slice:
            start = jnp.clip(q_offset + qs + block_q - span, 0, Sk - span)
            ki = jax.lax.dynamic_slice_in_dim(k, start, span, axis=1)
            vi = jax.lax.dynamic_slice_in_dim(v, start, span, axis=1)
            col_pos = jax.lax.dynamic_slice_in_dim(kv_positions, start, span)
        else:
            ki, vi, col_pos = k, v, kv_positions
        out = _block_attend(qi, ki, vi, row_pos, col_pos,
                            causal=causal, window=window)
        return None, out

    _, outs = jax.lax.scan(body, None,
                           (jnp.arange(n_blk, dtype=jnp.int32), qb),
                           unroll=n_blk if unroll else 1)
    vd = v.shape[-1]          # may differ from q head dim (MLA)
    out = jnp.moveaxis(outs, 0, 1).reshape(B, n_blk * block_q, K, g, vd)
    return out[:, :Sq].reshape(B, Sq, H, vd)


# ------------------------------------------------------------------- GQA ---

def gqa_params(key, cfg, dtype):
    D, H, K, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    ks = split_keys(key, 4)
    p = {
        "wq": dense_init(ks[0], (D, H * hd), dtype),
        "wk": dense_init(ks[1], (D, K * hd), dtype),
        "wv": dense_init(ks[2], (D, K * hd), dtype),
        "wo": dense_init(ks[3], (H * hd, D), dtype),
    }
    if cfg.bias:
        p["bq"] = jnp.zeros((H * hd,), dtype)
        p["bk"] = jnp.zeros((K * hd,), dtype)
        p["bv"] = jnp.zeros((K * hd,), dtype)
    return p


def _qkv(p, x, cfg):
    B, S, _ = x.shape
    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    return (q.reshape(B, S, H, hd), k.reshape(B, S, K, hd),
            v.reshape(B, S, K, hd))


def gqa_forward(p, x, cfg, *, window: int = 0, positions=None,
                mrope_pos=None, causal: bool = True, q_offset: int = 0,
                yarn=None):
    """Full-sequence (train/prefill) GQA.  Returns (y, (k, v)) so callers can
    build KV caches from prefill.  ``yarn``: the layer's YaRN table
    (``backbone.layer_yarn``), None for plain RoPE."""
    B, S, _ = x.shape
    q, k, v = _qkv(p, x, cfg)
    if cfg.rope_kind == "rope":
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(S) + q_offset, (B, S))
        q = apply_rope(q, positions, cfg.rope_theta, yarn)
        k = apply_rope(k, positions, cfg.rope_theta, yarn)
    elif cfg.rope_kind == "mrope":
        q = apply_mrope(q, mrope_pos, cfg.rope_theta)
        k = apply_mrope(k, mrope_pos, cfg.rope_theta)
    q, k, v = constrain_attn(q, k, v)
    y = dispatch.attention(q, k, v, causal=causal, window=window,
                           q_offset=q_offset, unroll=cfg.unroll_scans)
    return y.reshape(B, S, -1) @ p["wo"], (k, v)


def gqa_cross_forward(p, x, k, v, cfg):
    """Cross-attention (decoder x over encoder k/v), no mask."""
    B, S, _ = x.shape
    H, hd = cfg.n_heads, cfg.hd
    q = (x @ p["wq"]).reshape(B, S, H, hd)
    y = dispatch.attention(q, k, v, causal=False, unroll=cfg.unroll_scans)
    return y.reshape(B, S, -1) @ p["wo"]


def gqa_decode(p, x, cache_k, cache_v, cache_pos, pos, cfg, *,
               window: int = 0, mrope_pos=None, yarn=None):
    """One-token decode.  x: [B, 1, D]; cache_[kv]: [B, Sc, K, hd];
    cache_pos: [Sc] absolute position per slot (-1 = empty); pos: scalar
    or [B] (one decode cursor per row).

    Keys are stored *already rotated*; the new KV is written at slot
    ``pos % Sc`` (ring buffer; for full caches Sc >= S so slot == pos).

    With per-row ``pos`` (the continuous-batching engine's slot pool)
    each row writes its own slot and masks against its own cursor.  The
    rows still share one ``cache_pos``, which is only consistent when
    the ring never wraps (Sc > max pos): slot ``s`` then holds position
    ``s`` for every row that wrote it, so a freshly-admitted row at a
    low cursor masks out exactly the high-position slots it has not
    written yet.  Returns (y, new_k, new_v, new_cache_pos)."""
    B = x.shape[0]
    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q, k, v = _qkv(p, x, cfg)
    pos = jnp.asarray(pos)
    per_row = pos.ndim == 1
    posb = pos[:, None] if per_row \
        else jnp.broadcast_to(pos[None, None], (B, 1))
    if cfg.rope_kind == "rope":
        q = apply_rope(q, posb, cfg.rope_theta, yarn)
        k = apply_rope(k, posb, cfg.rope_theta, yarn)
    elif cfg.rope_kind == "mrope":
        mp = mrope_pos if mrope_pos is not None else jnp.stack([posb] * 3)
        q = apply_mrope(q, mp, cfg.rope_theta)
        k = apply_mrope(k, mp, cfg.rope_theta)
    Sc = cache_k.shape[1]
    slot = pos % Sc
    if per_row:
        rows = jnp.arange(B)
        cache_k = cache_k.at[rows, slot].set(k[:, 0])
        cache_v = cache_v.at[rows, slot].set(v[:, 0])
        # rows may scatter to the same slot, but under no-wraparound they
        # all write value s at index s, so the order is irrelevant
        cache_pos = cache_pos.at[slot].set(pos.astype(cache_pos.dtype))
    else:
        cache_k = jax.lax.dynamic_update_slice_in_dim(cache_k, k, slot,
                                                      axis=1)
        cache_v = jax.lax.dynamic_update_slice_in_dim(cache_v, v, slot,
                                                      axis=1)
        cache_pos = jax.lax.dynamic_update_slice_in_dim(
            cache_pos, pos[None].astype(cache_pos.dtype), slot, axis=0)

    g = H // K
    qh = q.reshape(B, 1, K, g, hd)
    scale = hd ** -0.5
    scores = jnp.einsum("bqkgh,bskh->bkgqs", qh, cache_k,
                        preferred_element_type=jnp.float32) * scale
    if per_row:
        mask = (cache_pos[None, :] <= posb) & (cache_pos >= 0)[None, :]
        if window:
            mask &= cache_pos[None, :] > posb - window
        mask = mask[:, None, None, None, :]
    else:
        mask = (cache_pos <= pos) & (cache_pos >= 0)
        if window:
            mask &= cache_pos > pos - window
        mask = mask[None, None, None, None]
    scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    y = jnp.einsum("bkgqs,bskh->bqkgh", probs.astype(cache_v.dtype), cache_v)
    y = y.reshape(B, 1, H * hd) @ p["wo"]
    return y, cache_k, cache_v, cache_pos


def gqa_decode_paged(p, x, arena_k, arena_v, page_table, pos, cfg, *,
                     window: int = 0, yarn=None):
    """One-token decode against a paged KV arena (``models/paging.py``).

    x: [B, 1, D]; arena_[kv]: [n_pages + 1, P, K * hd] (last page is the
    trash page); page_table: [B, max_blocks + 1] int32 with the last
    entry always trash; pos: [B] decode cursor per row.

    The new rotated KV is scattered into page ``table[row, pos // P]``
    at offset ``pos % P``; a cursor clamped to ``max_blocks * P`` indexes
    the trailing trash entry, so finished rows' zombie writes can never
    touch a page that may have been reallocated.  Attention itself goes
    through ``dispatch.paged_attention`` (gather reference or Pallas
    kernel), whose jnp route mirrors ``gqa_decode`` bit-for-bit.
    Returns (y, new_arena_k, new_arena_v)."""
    assert cfg.rope_kind != "mrope", "paged decode is rope/none only"
    B = x.shape[0]
    H, hd = cfg.n_heads, cfg.hd
    q, k, v = _qkv(p, x, cfg)
    posb = pos[:, None]
    if cfg.rope_kind == "rope":
        q = apply_rope(q, posb, cfg.rope_theta, yarn)
        k = apply_rope(k, posb, cfg.rope_theta, yarn)
    P = arena_k.shape[1]
    W = page_table.shape[1]
    rows = jnp.arange(B)
    blk = jnp.minimum(pos // P, W - 1)
    pg = page_table[rows, blk]
    off = pos % P
    # distinct live rows write distinct private pages (shared radix pages
    # cover only the block-aligned prompt prefix, below every decode
    # cursor); trash-page collisions between done rows are unread garbage
    arena_k = arena_k.at[pg, off].set(
        k.reshape(B, -1).astype(arena_k.dtype))
    arena_v = arena_v.at[pg, off].set(
        v.reshape(B, -1).astype(arena_v.dtype))
    y = dispatch.paged_attention(q[:, 0], arena_k, arena_v, page_table, pos,
                                 window=window)
    y = y.reshape(B, 1, H * hd) @ p["wo"]
    return y, arena_k, arena_v


def gqa_extend(p, x, prefix_k, prefix_v, cfg, *, q_offset: int,
               window: int = 0, yarn=None):
    """Prefill continuation over a cached prefix (radix-hit admission).

    x: [B, S, D] embeds of the *suffix* tokens (absolute positions
    ``q_offset .. q_offset + S``); prefix_[kv]: [B, q_offset, K, hd]
    already-rotated KVs gathered from cached pages.  Runs the identical
    math a full prefill would for the suffix rows -- per-query-row
    attention is independent of the other rows in the block, and the
    cached prefix KVs are exactly what full prefill produced -- so the
    suffix KVs/logits are bit-for-bit equal to re-prefilling from
    token 0.  Returns (y, (k, v)) with k/v the suffix KVs only."""
    assert cfg.rope_kind != "mrope", "paged extend is rope/none only"
    B, S, _ = x.shape
    q, k, v = _qkv(p, x, cfg)
    if cfg.rope_kind == "rope":
        positions = jnp.broadcast_to(jnp.arange(S) + q_offset, (B, S))
        q = apply_rope(q, positions, cfg.rope_theta, yarn)
        k = apply_rope(k, positions, cfg.rope_theta, yarn)
    q, k, v = constrain_attn(q, k, v)
    cat_k = jnp.concatenate([prefix_k.astype(k.dtype), k], axis=1)
    cat_v = jnp.concatenate([prefix_v.astype(v.dtype), v], axis=1)
    y = dispatch.attention(q, cat_k, cat_v, causal=True, window=window,
                           q_offset=q_offset, unroll=cfg.unroll_scans)
    return y.reshape(B, S, -1) @ p["wo"], (k, v)


# ------------------------------------------------------------------- MLA ---

def mla_params(key, cfg, dtype):
    m, D, H = cfg.mla, cfg.d_model, cfg.n_heads
    qk = m.qk_nope_dim + m.qk_rope_dim
    ks = split_keys(key, 6)
    return {
        "wq_a": dense_init(ks[0], (D, m.q_lora_rank), dtype),
        "q_norm": jnp.ones((m.q_lora_rank,), dtype),
        "wq_b": dense_init(ks[1], (m.q_lora_rank, H * qk), dtype),
        "wkv_a": dense_init(ks[2], (D, m.kv_lora_rank + m.qk_rope_dim), dtype),
        "kv_norm": jnp.ones((m.kv_lora_rank,), dtype),
        # stored factored so decode can run in the absorbed (latent) form
        "wk_b": dense_init(ks[3], (m.kv_lora_rank, H * m.qk_nope_dim), dtype),
        "wv_b": dense_init(ks[4], (m.kv_lora_rank, H * m.v_head_dim), dtype),
        "wo": dense_init(ks[5], (H * m.v_head_dim, D), dtype),
    }


def _mla_qkv_latent(p, x, cfg, positions):
    """Shared front half: queries + (normed) latent + rotated shared key."""
    from repro.models.common import rmsnorm
    m, H = cfg.mla, cfg.n_heads
    B, S, _ = x.shape
    q = rmsnorm(x @ p["wq_a"], p["q_norm"]) @ p["wq_b"]
    q = q.reshape(B, S, H, m.qk_nope_dim + m.qk_rope_dim)
    q_nope, q_rope = jnp.split(q, [m.qk_nope_dim], axis=-1)
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    kv = x @ p["wkv_a"]
    c_kv, k_rope = jnp.split(kv, [m.kv_lora_rank], axis=-1)
    c_kv = rmsnorm(c_kv, p["kv_norm"])
    k_rope = apply_rope(k_rope[:, :, None, :], positions, cfg.rope_theta)
    return q_nope, q_rope, c_kv, k_rope[:, :, 0, :]


def mla_forward(p, x, cfg, *, q_offset: int = 0):
    """Naive (expanded) MLA for train/prefill.  Returns (y, (c_kv, k_rope))
    so prefill can populate the latent cache."""
    m, H = cfg.mla, cfg.n_heads
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S) + q_offset, (B, S))
    q_nope, q_rope, c_kv, k_rope = _mla_qkv_latent(p, x, cfg, positions)
    k_nope = (c_kv @ p["wk_b"]).reshape(B, S, H, m.qk_nope_dim)
    v = (c_kv @ p["wv_b"]).reshape(B, S, H, m.v_head_dim)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                  (B, S, H, m.qk_rope_dim))], axis=-1)
    q, k, v = constrain_attn(q, k, v)
    # v_head_dim != qk dim, so dispatch falls back to the dim-agnostic
    # chunked path (the flash kernel assumes symmetric head dims)
    y = dispatch.attention(q, k, v, causal=True, q_offset=q_offset,
                           unroll=cfg.unroll_scans)
    return y.reshape(B, S, -1) @ p["wo"], (c_kv, k_rope)


def mla_decode(p, x, cache_ckv, cache_krope, cache_pos, pos, cfg):
    """Absorbed-form MLA decode: attention runs entirely in the latent space.
    cache_ckv: [B, Sc, r]; cache_krope: [B, Sc, rope]."""
    m, H = cfg.mla, cfg.n_heads
    B = x.shape[0]
    posb = jnp.broadcast_to(jnp.asarray(pos)[None, None], (B, 1))
    q_nope, q_rope, c_kv, k_rope = _mla_qkv_latent(p, x, cfg, posb)
    Sc = cache_ckv.shape[1]
    slot = jnp.asarray(pos) % Sc
    cache_ckv = jax.lax.dynamic_update_slice_in_dim(cache_ckv, c_kv, slot, 1)
    cache_krope = jax.lax.dynamic_update_slice_in_dim(
        cache_krope, k_rope, slot, 1)
    cache_pos = jax.lax.dynamic_update_slice_in_dim(
        cache_pos, jnp.asarray(pos)[None].astype(cache_pos.dtype), slot, 0)

    wk_b = p["wk_b"].reshape(m.kv_lora_rank, H, m.qk_nope_dim)
    # absorb W_UK into the query: q_lat[b,h,r] = sum_n q_nope[b,h,n] wk_b[r,h,n]
    q_lat = jnp.einsum("bqhn,rhn->bqhr", q_nope, wk_b)
    scale = (m.qk_nope_dim + m.qk_rope_dim) ** -0.5
    scores = (jnp.einsum("bqhr,bsr->bhqs", q_lat, cache_ckv,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bqhr,bsr->bhqs", q_rope, cache_krope,
                           preferred_element_type=jnp.float32)) * scale
    mask = (cache_pos <= pos) & (cache_pos >= 0)
    scores = jnp.where(mask[None, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out_lat = jnp.einsum("bhqs,bsr->bqhr", probs.astype(cache_ckv.dtype),
                         cache_ckv)
    wv_b = p["wv_b"].reshape(m.kv_lora_rank, H, m.v_head_dim)
    y = jnp.einsum("bqhr,rhv->bqhv", out_lat, wv_b)
    y = y.reshape(B, 1, H * m.v_head_dim) @ p["wo"]
    return y, cache_ckv, cache_krope, cache_pos
