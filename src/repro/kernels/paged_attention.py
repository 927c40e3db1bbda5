"""Paged-attention decode kernel: one query token over a paged KV arena.

The paged layout (``models/paging.py``) stores KV in a fixed arena of
``n_pages + 1`` pages of ``P`` token slots (the last page is the trash
page); each batch row owns a page table of ``max_blocks + 1`` physical
page ids mapping logical block ``b`` -> arena page.  Decode attends one
query per row against the row's mapped pages only -- O(max_blocks * P)
per row regardless of arena size, which is what lets one arena back
hundreds of concurrent rows.

Two implementations behind ``repro.kernels.dispatch.paged_attention``:

* ``paged_attention_ref`` -- gather-then-attend in pure jnp, written to
  be *bit-for-bit identical* to the dense per-row ``gqa_decode`` path
  when the logical lengths match: the per-row page-table gather
  reassembles exactly the [B, S, K, hd] tensor the dense ring holds
  (garbage in not-yet-written slots is masked to ``NEG_INF`` whose
  ``exp`` underflows to exact 0.0), then runs the identical einsum /
  softmax / einsum sequence.  This is the ``jnp`` route and the parity
  oracle for the engine suite.
* ``paged_attention_kernel`` -- Pallas with ``PrefetchScalarGridSpec``:
  the page table and per-row cursors are scalar-prefetched so the KV
  BlockSpec index_map resolves ``table[row, block]`` at grid-fetch time
  -- each (row, kv-head) program streams only its own pages through
  VMEM with online-softmax (m, l, acc) scratch, never materializing the
  gathered [B, S, K, hd] intermediate.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def paged_attention_ref(q, arena_k, arena_v, page_table, pos, *,
                        window: int = 0):
    """q: [B, H, hd]; arena_[kv]: [n_pages + 1, P, K, hd];
    page_table: [B, max_blocks + 1] int32 (last entry trash, unread);
    pos: [B] int32 decode cursor per row -> [B, H, hd].

    Mirrors the dense ``gqa_decode`` math operation-for-operation
    (same einsum strings, f32 accumulation, softmax over the same
    logical axis) so paged == dense bitwise when S matches the ring.
    """
    B, H, hd = q.shape
    P, K = arena_k.shape[1], arena_k.shape[2]
    g = H // K
    mb = page_table.shape[1] - 1
    S = mb * P
    ks = arena_k[page_table[:, :mb]].reshape(B, S, K, hd)
    vs = arena_v[page_table[:, :mb]].reshape(B, S, K, hd)
    qh = q.reshape(B, 1, K, g, hd)
    scale = hd ** -0.5
    scores = jnp.einsum("bqkgh,bskh->bkgqs", qh, ks,
                        preferred_element_type=jnp.float32) * scale
    cols = jnp.arange(S)
    posb = pos[:, None]
    mask = cols[None, :] <= posb
    if window:
        mask &= cols[None, :] > posb - window
    scores = jnp.where(mask[:, None, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    y = jnp.einsum("bkgqs,bskh->bqkgh", probs.astype(vs.dtype), vs)
    return y.reshape(B, H, hd)


def _kernel(pt_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
            m_ref, l_ref, acc_ref, *, P: int, n_blocks: int, scale: float,
            window: int):
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref[...], NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref[...])
        acc_ref[...] = jnp.zeros_like(acc_ref[...])

    @pl.when(j * P <= pos_ref[b])       # block holds at least one valid col
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)               # [g, hd]
        k = k_ref[0].astype(jnp.float32)                  # [P, hd]
        v = v_ref[0].astype(jnp.float32)
        s = (q @ k.T) * scale                             # [g, P]
        g_dim = s.shape[0]
        cols = j * P + jax.lax.broadcasted_iota(jnp.int32, (g_dim, P), 1)
        mask = cols <= pos_ref[b]
        if window:
            mask &= cols > pos_ref[b] - window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        # a fully-masked tile (window slid past it) keeps m at NEG_INF;
        # exp(s - m) would be exp(0) there, so re-zero under the mask
        p = jnp.where(mask, jnp.exp(s - m_new[:, None]), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + p @ v
        m_ref[...] = m_new

    @pl.when(j == n_blocks - 1)
    def _fin():
        denom = jnp.maximum(l_ref[...], 1e-30)[:, None]
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def paged_attention_kernel(q, arena_k, arena_v, page_table, pos, *,
                           window: int = 0, interpret: bool = False):
    """Pallas paged decode: same contract as ``paged_attention_ref``.

    Grid (B, K, max_blocks), pages innermost; ``page_table``/``pos``
    ride in as scalar prefetch so the KV index_map picks the physical
    page per grid step -- the arena is indexed in place, no per-row
    gather copy ever exists.
    """
    B, H, hd = q.shape
    P, K = arena_k.shape[1], arena_k.shape[2]
    g = H // K
    mb = page_table.shape[1] - 1
    qh = q.reshape(B, K, g, hd)

    # [pages, P, K, hd] -> [pages, P, K * hd] is free (contiguous), and
    # makes a (P, hd) tile the block's last two dims -- (8, 128)-aligned
    # for Mosaic -- with kv head h at lane block h
    arena_k = arena_k.reshape(*arena_k.shape[:2], K * hd)
    arena_v = arena_v.reshape(*arena_v.shape[:2], K * hd)

    def kv_index(b, h, j, pt_ref, pos_ref):
        return pt_ref[b, j], 0, h

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, K, mb),
        in_specs=[
            pl.BlockSpec((1, 1, g, hd),
                         lambda b, h, j, pt_ref, pos_ref: (b, h, 0, 0)),
            pl.BlockSpec((1, P, hd), kv_index),
            pl.BlockSpec((1, P, hd), kv_index),
        ],
        out_specs=pl.BlockSpec((1, 1, g, hd),
                               lambda b, h, j, pt_ref, pos_ref: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g,), jnp.float32),
            pltpu.VMEM((g,), jnp.float32),
            pltpu.VMEM((g, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, P=P, n_blocks=mb, scale=hd ** -0.5,
                          window=window),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, g, hd), q.dtype),
        interpret=interpret,
    )(page_table.astype(jnp.int32), pos.astype(jnp.int32),
      qh, arena_k, arena_v)
    return out.reshape(B, H, hd)
