"""Fused Gumbel-max sampling kernel: categorical draw + chosen logprob.

The generator's per-decode-step hot spot: ``jax.random.categorical`` plus a
``log_softmax`` gather builds two full [B, V] fp32 arrays per token.  This
kernel streams vocab tiles once, maintaining four online accumulators per
row -- softmax ``(m, s)``, the running Gumbel-max ``best``/``best_tok`` and
the chosen token's scaled logit -- so the output is ``(token, log
pi_T(token))`` with no [B, V] intermediate.  Temperature is applied
in-kernel (``temperature == 0`` is greedy argmax scored at T=1, matching the
previous sampler's semantics).

Noise is a counter-based hash (splitmix-style, keyed by the PRNG key data):
position ``(row, col)`` always hashes to the same uniform regardless of tile
shape, which is what lets the Pallas kernel, the streamed-jnp fallback and
the dense reference (``ref.fused_sample_ref``) produce *identical* tokens
under the same key.  Grid: (B/bb, V/bv), vocab innermost.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.online import NEG_INF, online_softmax_step


def key_data_u32(key) -> jax.Array:
    """uint32[2] words from either a raw PRNGKey array or a typed key."""
    if jnp.issubdtype(key.dtype, jnp.unsignedinteger) or \
            jnp.issubdtype(key.dtype, jnp.signedinteger):
        return key.astype(jnp.uint32).reshape(-1)[:2]
    return jax.random.key_data(key).astype(jnp.uint32).reshape(-1)[:2]


def _mix(x):
    """splitmix32-style finalizer on uint32."""
    x = x ^ (x >> jnp.uint32(16))
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> jnp.uint32(15))
    x = x * jnp.uint32(0x846CA68B)
    return x ^ (x >> jnp.uint32(16))


def hash_uniform(rows, cols, k0, k1):
    """Position-keyed uniform in (0, 1).  Rows and cols are mixed in two
    separate stages (hash(row) folded with col) rather than a linear
    ``row * V + col`` counter, which would wrap in uint32 and hand rows
    2^32/V apart bit-identical noise at V = 256k.  Pure uint32 jnp ops, so
    the same bits come out of the Pallas body, the scan fallback and the
    dense reference."""
    x = _mix(rows.astype(jnp.uint32) * jnp.uint32(0x9E3779B9) + k0)
    x = _mix(x + cols.astype(jnp.uint32) * jnp.uint32(0x85EBCA6B) + k1)
    # 24 random bits fit int32 exactly; Mosaic casts int32 -> f32 but
    # not uint32 -> f32
    mant = (x >> jnp.uint32(8)).astype(jnp.int32).astype(jnp.float32)
    return (mant + 0.5) * (1.0 / (1 << 24))


def gumbel_noise(rows, cols, k0, k1):
    """Standard Gumbel at absolute positions (rows, cols) of a [B, V] draw."""
    return -jnp.log(-jnp.log(hash_uniform(rows, cols, k0, k1)))


def _kernel(key_ref, logits_ref, tok_ref, lp_ref, m_ref, s_ref, best_ref,
            btok_ref, blog_ref, *, bb: int, bv: int, n_vblocks: int,
            v_true: int, inv_temp: float, noisy: bool):
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref[...], NEG_INF)
        s_ref[...] = jnp.zeros_like(s_ref[...])
        best_ref[...] = jnp.full_like(best_ref[...], -jnp.inf)
        btok_ref[...] = jnp.zeros_like(btok_ref[...])
        blog_ref[...] = jnp.full_like(blog_ref[...], NEG_INF)

    block = logits_ref[...].astype(jnp.float32) * inv_temp   # [bb, bv]
    cols = j * bv + jax.lax.broadcasted_iota(jnp.int32, (bb, bv), 1)
    valid = cols < v_true

    # online softmax stats of the *scaled* logits
    m_new, s_new, masked = online_softmax_step(m_ref[...], s_ref[...],
                                               block, valid)
    s_ref[...] = s_new
    m_ref[...] = m_new

    # running Gumbel-max (greedy argmax when noise is off)
    z = masked
    if noisy:
        rows = i * bb + jax.lax.broadcasted_iota(jnp.int32, (bb, bv), 0)
        z = z + gumbel_noise(rows, cols, key_ref[0], key_ref[1])
    z = jnp.where(valid, z, -jnp.inf)
    tile_best = jnp.max(z, axis=-1)
    # first argmax and its logit by iota / one-hot select: Mosaic lowers
    # neither argmax nor a lane gather
    at_best = z == tile_best[:, None]
    tile_tok = jnp.min(jnp.where(at_best, cols, jnp.iinfo(jnp.int32).max),
                       axis=-1)
    # strict > keeps the earliest tile on ties -> global first-argmax
    better = tile_best > best_ref[...]
    chosen = jnp.max(jnp.where(cols == tile_tok[:, None], block, -jnp.inf),
                     axis=-1)
    btok_ref[...] = jnp.where(better, tile_tok, btok_ref[...])
    blog_ref[...] = jnp.where(better, chosen, blog_ref[...])
    best_ref[...] = jnp.maximum(best_ref[...], tile_best)

    @pl.when(j == n_vblocks - 1)
    def _fin():
        tok_ref[...] = btok_ref[...][:, None]
        # subtract m before log s (extreme-|m| fp32 absorption, see
        # fused_logprob)
        lp_ref[...] = ((blog_ref[...] - m_ref[...])
                       - jnp.log(s_ref[...]))[:, None]


def fused_sample(logits, key, *, temperature: float = 1.0,
                 block_b: int = 256, block_v: int = 2048,
                 interpret: bool = False):
    """logits: [B, V]; key: PRNGKey -> (tokens [B] int32, logprob [B] fp32).

    ``logprob`` is the chosen token's log-prob under the sampling
    distribution (temperature-scaled softmax; plain softmax when
    ``temperature == 0``), exactly what the trainer needs as behavior mu.
    """
    B, V = logits.shape
    bb = min(block_b, B)
    bv = min(block_v, V)
    pad_b = (-B) % bb
    pad_v = (-V) % bv
    if pad_b or pad_v:
        logits = jnp.pad(logits, ((0, pad_b), (0, pad_v)),
                         constant_values=NEG_INF)
    Bp, Vp = logits.shape
    n_vblocks = Vp // bv
    kd = key_data_u32(key)
    tok, lp = pl.pallas_call(
        functools.partial(
            _kernel, bb=bb, bv=bv, n_vblocks=n_vblocks, v_true=V,
            inv_temp=1.0 / temperature if temperature > 0.0 else 1.0,
            noisy=temperature > 0.0),
        grid=(Bp // bb, n_vblocks),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),   # scalar key words
            pl.BlockSpec((bb, bv), lambda i, j: (i, j)),
        ],
        # [B, 1] columns: a 1-D (bb,) block clashes with XLA's tiled
        # layout of a longer 1-D array (see fused_logprob)
        out_specs=[pl.BlockSpec((bb, 1), lambda i, j: (i, 0)),
                   pl.BlockSpec((bb, 1), lambda i, j: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((Bp, 1), jnp.int32),
                   jax.ShapeDtypeStruct((Bp, 1), jnp.float32)],
        scratch_shapes=[
            pltpu.VMEM((bb,), jnp.float32),
            pltpu.VMEM((bb,), jnp.float32),
            pltpu.VMEM((bb,), jnp.float32),
            pltpu.VMEM((bb,), jnp.int32),
            pltpu.VMEM((bb,), jnp.float32),
        ],
        interpret=interpret,
    )(kd, logits)
    return tok[:B, 0], lp[:B, 0]
