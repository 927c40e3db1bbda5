"""Fused per-token log-prob kernel: log pi(y_t) over a large vocabulary.

The RL trainer's hot spot (paper Sec. 6: per-token importance ratios need
log pi and log mu): computing ``log_softmax(logits)[token]`` naively
materializes a [T, V] fp32 log-softmax (V up to 256k here).  This kernel
streams vocab tiles through VMEM with an online (max, sumexp) reduction and
picks out the target logit on the fly -- one pass, no [T, V] intermediate.

Grid: (T/bt, V/bv); vocab is the *innermost* (sequential) axis so the
scratch accumulators carry across vocab tiles for a fixed token tile.

``fused_logprob(..., return_stats=True)`` also emits the per-row online
``(m, s)`` stats (``logZ = m + log s``), which are exactly the residuals the
custom-VJP backward needs: ``d logits = (onehot - softmax) * g`` is
computable tile-by-tile from ``exp(logits - logZ)`` without ever holding a
full-vocab fp32 softmax (``fused_logprob_bwd``).  Routing between the
compiled / interpreted / jnp-streamed variants lives in ``dispatch.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.online import NEG_INF, online_softmax_step


def _kernel(tokens_ref, logits_ref, out_ref, m_out, s_out, m_ref, s_ref,
            t_ref, *, bt: int, bv: int, n_vblocks: int, v_true: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref[...], NEG_INF)
        s_ref[...] = jnp.zeros_like(s_ref[...])
        t_ref[...] = jnp.full_like(t_ref[...], NEG_INF)

    block = logits_ref[...].astype(jnp.float32)          # [bt, bv]
    # valid masks padded vocab columns out of both the max and the sumexp
    # (they must not contribute even when every real logit == NEG_INF)
    cols = j * bv + jax.lax.broadcasted_iota(jnp.int32, (bt, bv), 1)
    m_new, s_new, _ = online_softmax_step(m_ref[...], s_ref[...], block,
                                          cols < v_true)
    m_ref[...] = m_new
    s_ref[...] = s_new

    # one-hot select of the target logit (Mosaic has no lowering for a
    # lane gather): the max over a single unmasked column is that value
    hit = cols == tokens_ref[...]                        # [bt, 1] ids
    vals = jnp.max(jnp.where(hit, block, -jnp.inf), axis=1)
    t_ref[...] = jnp.where(jnp.any(hit, axis=1), vals, t_ref[...])

    @pl.when(j == n_vblocks - 1)
    def _fin():
        # subtract m before log s: with extreme logits (|m| ~ 1e30) the sum
        # m + log s absorbs log s entirely in fp32
        out_ref[...] = ((t_ref[...] - m_ref[...])
                        - jnp.log(s_ref[...]))[:, None]
        m_out[...] = m_ref[...][:, None]
        s_out[...] = s_ref[...][:, None]


def fused_logprob(logits, tokens, *, block_t: int = 256,
                  block_v: int = 2048, interpret: bool = False,
                  return_stats: bool = False):
    """logits: [T, V]; tokens: [T] int32 -> logprobs [T] fp32.

    With ``return_stats=True`` returns ``(logprobs, m, s)`` where
    ``logZ = m + log s`` (the VJP residuals).  Per-row operands cross
    HBM as [T, 1] columns: a 1-D (bt,) block clashes with the tiled
    layout XLA gives a 1-D array longer than one block.
    """
    T, V = logits.shape
    bt = min(block_t, T)
    bv = min(block_v, V)
    pad_t = (-T) % bt
    pad_v = (-V) % bv
    if pad_t or pad_v:
        logits = jnp.pad(logits, ((0, pad_t), (0, pad_v)),
                         constant_values=NEG_INF)
        tokens = jnp.pad(tokens, (0, pad_t))
    Tp, Vp = logits.shape
    n_vblocks = Vp // bv
    col = pl.BlockSpec((bt, 1), lambda i, j: (i, 0))
    out, m, s = pl.pallas_call(
        functools.partial(_kernel, bt=bt, bv=bv, n_vblocks=n_vblocks,
                          v_true=V),
        grid=(Tp // bt, n_vblocks),
        in_specs=[col, pl.BlockSpec((bt, bv), lambda i, j: (i, j))],
        out_specs=[col, col, col],
        out_shape=[jax.ShapeDtypeStruct((Tp, 1), jnp.float32)] * 3,
        scratch_shapes=[
            pltpu.VMEM((bt,), jnp.float32),
            pltpu.VMEM((bt,), jnp.float32),
            pltpu.VMEM((bt,), jnp.float32),
        ],
        interpret=interpret,
    )(tokens[:, None], logits)
    out, m, s = out[:T, 0], m[:T, 0], s[:T, 0]
    if return_stats:
        return out, m, s
    return out


def _bwd_kernel(tokens_ref, logits_ref, m_ref, ls_ref, g_ref, dl_ref, *,
                bt: int, bv: int):
    """d logits = g * (onehot(token) - softmax) for one [bt, bv] tile.

    softmax = exp((logits - m) - log s), subtracted sequentially so extreme
    m does not absorb log s (same fp32 caveat as the forward)."""
    j = pl.program_id(1)
    block = logits_ref[...].astype(jnp.float32)
    p = jnp.exp((block - m_ref[...]) - ls_ref[...])      # [bt, 1] stats
    local = tokens_ref[...] - j * bv
    cols = jax.lax.broadcasted_iota(jnp.int32, (bt, bv), 1)
    onehot = (cols == local).astype(jnp.float32)
    dl_ref[...] = ((onehot - p) * g_ref[...]).astype(dl_ref.dtype)


def fused_logprob_bwd(logits, tokens, m, log_s, g, *, block_t: int = 256,
                      block_v: int = 2048, interpret: bool = False):
    """Streaming VJP: logits [T, V], tokens/m/log_s/g [T] -> dlogits [T, V].

    Each grid cell is independent (no carry): the tile's softmax is
    reconstructed from the saved online stats, so peak live memory is one
    [bt, bv] tile plus the (unavoidable) dlogits output.
    """
    T, V = logits.shape
    bt = min(block_t, T)
    bv = min(block_v, V)
    pad_t = (-T) % bt
    pad_v = (-V) % bv
    if pad_t or pad_v:
        logits = jnp.pad(logits, ((0, pad_t), (0, pad_v)),
                         constant_values=NEG_INF)
        tokens = jnp.pad(tokens, (0, pad_t))
        m = jnp.pad(m, (0, pad_t))
        log_s = jnp.pad(log_s, (0, pad_t))
        g = jnp.pad(g, (0, pad_t))
    Tp, Vp = logits.shape
    col = pl.BlockSpec((bt, 1), lambda i, j: (i, 0))
    out = pl.pallas_call(
        functools.partial(_bwd_kernel, bt=bt, bv=bv),
        grid=(Tp // bt, Vp // bv),
        in_specs=[col, pl.BlockSpec((bt, bv), lambda i, j: (i, j)),
                  col, col, col],
        out_specs=pl.BlockSpec((bt, bv), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Tp, Vp), logits.dtype),
        interpret=interpret,
    )(tokens[:, None], logits, m[:, None], log_s[:, None], g[:, None])
    return out[:T, :V]
