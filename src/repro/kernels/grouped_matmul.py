"""Grouped matrix products for the expert layer: rows sorted by group,
each group's rows times its own weight matrix.

The expert layer (``models/ffn.py``) lays its token-expert pairs out in
groups aligned to a tile of ``tm`` rows: group ``g`` starts on a tile
boundary and holds ``sizes[g]`` rows, its last tile's remaining rows zero.
So each row tile belongs to one group, and the kernels need only the group
of each tile (``tile_group``) and the number of tiles in use (``n_used``),
both scalar-prefetched.  Tiles past ``n_used`` reuse the last used tile's
block indices, so they fetch nothing and compute nothing; their rows of
the result are left unwritten, and the layer never reads them.

* ``gmm(lhs [M, K], rhs [G, K, N])`` -> [M, N]: each tile's rows times its
  group's matrix; ``transpose_rhs`` takes rhs as [G, N, K] and multiplies
  by its transpose (the gradient with respect to ``lhs``).
* ``tgmm(lhs [M, K], dout [M, N])`` -> [G, K, N]: each group's rows of
  ``lhs``, transposed, times its rows of ``dout`` (the gradient with
  respect to ``rhs``); a group with no rows gets zeros.

Grid ``(column tiles, row tiles)``, both in order.  The weight block is a
group's whole matrix, or a band of ``tn`` columns of it where the whole
would not fit ``_WEIGHT_BLOCK_BYTES``, so consecutive tiles of one group
fetch it once per band.  Operands are rounded to bfloat16 in the kernel
and products accumulate in float32: the chip's default precision for
float32 matrix products, as XLA runs every other product of the program.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# one buffer of the weight block; the pipeline holds two
_WEIGHT_BLOCK_BYTES = 8 << 20
_MAX_ROW_TILE = 256


def row_tile(mean_rows: float) -> int:
    """Rows per tile for groups of about ``mean_rows`` rows: the power of
    two from 8 to ``_MAX_ROW_TILE`` nearest above."""
    tm = 8
    while tm < mean_rows and tm < _MAX_ROW_TILE:
        tm *= 2
    return tm


def col_tile(K: int, N: int, itemsize: int = 4) -> int:
    """Columns of a weight block [K, tn]: all ``N`` where the block fits
    ``_WEIGHT_BLOCK_BYTES``, else the widest multiple of 128 dividing
    ``N`` that fits; 0 where none does."""
    if K * N * itemsize <= _WEIGHT_BLOCK_BYTES:
        return N
    fits = [tn for tn in range(128, N, 128)
            if N % tn == 0 and K * tn * itemsize <= _WEIGHT_BLOCK_BYTES]
    return max(fits, default=0)


def tile_groups(sizes, tm: int, n_tiles: int):
    """(group of each of ``n_tiles`` row tiles [n_tiles] int32, tiles in
    use [1] int32) for groups of ``sizes`` rows laid out tile-aligned.
    Tiles past those in use get the last used tile's group."""
    G = sizes.shape[0]
    ends = jnp.cumsum((sizes + tm - 1) // tm)
    n_used = ends[-1]
    t = jnp.minimum(jnp.arange(n_tiles), jnp.maximum(n_used - 1, 0))
    tg = jnp.minimum(jnp.searchsorted(ends, t, side="right"), G - 1)
    return tg.astype(jnp.int32), n_used.reshape(1).astype(jnp.int32)


def _row(i, nu_ref):
    """The row tile a grid step works on: its own, or past the tiles in
    use, the last one in use (same block indices: nothing is fetched)."""
    return jnp.minimum(i, jnp.maximum(nu_ref[0] - 1, 0))


def _bf16_dot(a, b, dims):
    return jax.lax.dot_general(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                               (dims, ((), ())),
                               preferred_element_type=jnp.float32)


def _gmm_kernel(tg_ref, nu_ref, lhs_ref, rhs_ref, out_ref, *,
                transpose_rhs: bool):
    @pl.when(pl.program_id(1) < nu_ref[0])
    def _():
        dims = ((1,), (1,)) if transpose_rhs else ((1,), (0,))
        out_ref[...] = _bf16_dot(lhs_ref[...], rhs_ref[0],
                                 dims).astype(out_ref.dtype)


def _vmem_limit(*block_bytes) -> int:
    """Double-buffered blocks plus their bfloat16 copies, with room."""
    return int(3 * sum(block_bytes)) + (8 << 20)


def gmm(lhs, rhs, tile_group, n_used, *, tm: int, transpose_rhs=False,
        interpret: bool = False):
    """lhs: [M, K] with M a multiple of ``tm``; rhs: [G, K, N] (or
    [G, N, K] with ``transpose_rhs``) -> [M, N] in lhs's dtype."""
    M, K = lhs.shape
    G = rhs.shape[0]
    N = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    tn = col_tile(K, N, rhs.dtype.itemsize)
    assert tn and M % tm == 0, (M, K, N, tm, tn)

    def rhs_index(n, i, tg, nu):
        g = tg[_row(i, nu)]
        return (g, n, 0) if transpose_rhs else (g, 0, n)

    rhs_block = (1, tn, K) if transpose_rhs else (1, K, tn)
    isz = lhs.dtype.itemsize
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(N // tn, M // tm),
        in_specs=[pl.BlockSpec((tm, K),
                               lambda n, i, tg, nu: (_row(i, nu), 0)),
                  pl.BlockSpec(rhs_block, rhs_index)],
        out_specs=pl.BlockSpec((tm, tn),
                               lambda n, i, tg, nu: (_row(i, nu), n)),
    )
    return pl.pallas_call(
        functools.partial(_gmm_kernel, transpose_rhs=transpose_rhs),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, N), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(tm * K * isz, K * tn * isz,
                                         tm * tn * isz)),
        interpret=interpret,
    )(tile_group, n_used, lhs, rhs)


def _tgmm_kernel(tg_ref, nu_ref, lhs_ref, dout_ref, out_ref):
    i = pl.program_id(1)

    @pl.when(i < nu_ref[0])
    def _():
        @pl.when((i == 0) | (tg_ref[i] != tg_ref[jnp.maximum(i - 1, 0)]))
        def _():
            out_ref[...] = jnp.zeros_like(out_ref)

        out_ref[0] += _bf16_dot(lhs_ref[...], dout_ref[...], ((0,), (0,)))


def tgmm(lhs, dout, sizes, tile_group, n_used, *, tm: int,
         interpret: bool = False):
    """lhs: [M, K]; dout: [M, N]; sizes: [G] rows per group ->
    [G, K, N] float32, each group's ``lhs`` rows transposed times its
    ``dout`` rows."""
    M, K = lhs.shape
    N = dout.shape[1]
    G = sizes.shape[0]
    tn = col_tile(K, N)
    assert tn and M % tm == 0, (M, K, N, tm, tn)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(N // tn, M // tm),
        in_specs=[pl.BlockSpec((tm, K),
                               lambda n, i, tg, nu: (_row(i, nu), 0)),
                  pl.BlockSpec((tm, tn),
                               lambda n, i, tg, nu: (_row(i, nu), n))],
        out_specs=pl.BlockSpec(
            (1, K, tn), lambda n, i, tg, nu: (tg[_row(i, nu)], 0, n)),
    )
    out = pl.pallas_call(
        _tgmm_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((G, K, N), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(tm * K * lhs.dtype.itemsize,
                                         tm * tn * dout.dtype.itemsize,
                                         K * tn * 4)),
        interpret=interpret,
    )(tile_group, n_used, lhs, dout)
    # a group with no rows was never visited: its block holds no result
    return jnp.where((sizes > 0)[:, None, None], out, 0.0)


def gmm_ref(lhs, rhs, sizes, *, tm: int, transpose_rhs=False):
    """The plain route: ``jax.lax.ragged_dot`` over the tile-aligned
    groups (each group's rows padded to whole tiles, the padding zero)."""
    if transpose_rhs:
        rhs = jnp.swapaxes(rhs, 1, 2)
    padded = ((sizes + tm - 1) // tm * tm).astype(jnp.int32)
    return jax.lax.ragged_dot(lhs, rhs, padded)
