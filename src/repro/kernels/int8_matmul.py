"""int8 x int8-weight matmul kernel with per-column dequant scales.

TPU-native analogue of the paper's fp8 generator quantization (Sec. 4.3):
activations stay bf16/f32, weights are int8 with per-output-channel scales.
Grid: (M/bm, N/bn, K/bk), K innermost; fp32 accumulator in VMEM scratch,
dequant applied once at the final K tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, wq_ref, scale_ref, o_ref, acc_ref, *, n_kblocks: int):
    kblk = pl.program_id(2)

    @pl.when(kblk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref[...])

    x = x_ref[...].astype(jnp.float32)            # [bm, bk]
    w = wq_ref[...].astype(jnp.float32)           # [bk, bn] (int8 -> f32)
    acc_ref[...] += x @ w

    @pl.when(kblk == n_kblocks - 1)
    def _fin():
        o_ref[...] = (acc_ref[...] * scale_ref[...]).astype(o_ref.dtype)


def int8_matmul(x, w_q, scale, *, block_m: int = 256, block_n: int = 256,
                block_k: int = 512, interpret: bool = False,
                out_dtype=jnp.float32):
    """x: [M, K] float; w_q: [K, N] int8; scale: [N] f32 -> [M, N]."""
    M, K = x.shape
    N = w_q.shape[1]
    bm, bn, bk = min(block_m, M), min(block_n, N), min(block_k, K)
    pm, pn, pk = (-M) % bm, (-N) % bn, (-K) % bk
    if pm or pk:
        x = jnp.pad(x, ((0, pm), (0, pk)))
    if pk or pn:
        w_q = jnp.pad(w_q, ((0, pk), (0, pn)))
    if pn:
        scale = jnp.pad(scale, (0, pn))
    Mp, Kp = x.shape
    Np = w_q.shape[1]
    out = pl.pallas_call(
        functools.partial(_kernel, n_kblocks=Kp // bk),
        grid=(Mp // bm, Np // bn, Kp // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            # 2-D: a 1-D (bn,) block conflicts with XLA's T(1024) layout
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(x, w_q, scale.reshape(1, Np))
    return out[:M, :N]
