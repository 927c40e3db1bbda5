"""Causal flash attention kernel (GQA-aware), BlockSpec-tiled for VMEM.

Grid: (B*H, S/bq, S/bk) with the KV axis innermost; online-softmax
accumulators (m, l, acc) live in VMEM scratch and carry across KV tiles.
KV tiles with ``j > i`` are skipped entirely (causal); the GQA mapping is
done in the K/V index_map (query head h reads kv head h // group), so K/V
are never materialized per-query-head.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            bq: int, bk: int, n_kblocks: int, scale: float):
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref[...], NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref[...])
        acc_ref[...] = jnp.zeros_like(acc_ref[...])

    @pl.when(j * bk < (i + 1) * bq)    # KV tile starts at/before last row
    def _compute():
        q = q_ref[0].astype(jnp.float32)                  # [bq, hd]
        k = k_ref[0].astype(jnp.float32)                  # [bk, hd]
        v = v_ref[0].astype(jnp.float32)
        s = (q @ k.T) * scale                             # [bq, bk]
        rows = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        cols = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(cols <= rows, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + p @ v
        m_ref[...] = m_new

    @pl.when(j == n_kblocks - 1)
    def _fin():
        denom = jnp.maximum(l_ref[...], 1e-30)[:, None]
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def flash_attention(q, k, v, *, block_q: int = 256, block_k: int = 256,
                    interpret: bool = False):
    """q: [B, S, H, hd]; k/v: [B, S, K, hd] -> [B, S, H, hd].  Causal."""
    B, S, H, hd = q.shape
    K = k.shape[2]
    g = H // K
    scale = hd ** -0.5
    bq = min(block_q, S)
    bk = min(block_k, S)
    assert S % bq == 0 and S % bk == 0, (S, bq, bk)

    qf = jnp.moveaxis(q, 2, 1).reshape(B * H, S, hd)
    kf = jnp.moveaxis(k, 2, 1).reshape(B * K, S, hd)
    vf = jnp.moveaxis(v, 2, 1).reshape(B * K, S, hd)

    def kv_index(b, i, j):
        return (b // H) * K + (b % H) // g, j, 0

    out = pl.pallas_call(
        functools.partial(_kernel, bq=bq, bk=bk, n_kblocks=S // bk,
                          scale=scale),
        grid=(B * H, S // bq, S // bk),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, hd), kv_index),
            pl.BlockSpec((1, bk, hd), kv_index),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, S, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf)
    return jnp.moveaxis(out.reshape(B, H, S, hd), 1, 2)
