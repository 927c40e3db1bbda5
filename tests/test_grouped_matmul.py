"""The grouped-matmul kernels (``kernels/grouped_matmul.py``) in the
Pallas interpreter against ``jax.lax.ragged_dot``, and the dispatched
product's gradients through the kernel route's custom VJP.

The kernels round their operands to bfloat16 (the chip's default
precision for float32 products), so the plain route is given operands
rounded alike; what is left is float32 accumulation order."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import dispatch
from repro.kernels import grouped_matmul as gm


def _bf16(a):
    return a.astype(jnp.bfloat16).astype(jnp.float32)


def _layout(sizes, tm, K, seed=0, spare_tiles=2):
    """lhs [M, K]: group g's rows from a tile boundary, padding zero, and
    ``spare_tiles`` tiles past the last group's."""
    rng = np.random.default_rng(seed)
    M = (sum(-(-s // tm) for s in sizes) + spare_tiles) * tm
    lhs = np.zeros((M, K), np.float32)
    off = 0
    for s in sizes:
        lhs[off:off + s] = rng.standard_normal((s, K))
        off += -(-s // tm) * tm
    return jnp.asarray(lhs), jnp.asarray(sizes, jnp.int32)


def test_tile_groups_cover_each_group_in_order():
    tg, nu = gm.tile_groups(jnp.asarray([5, 0, 17, 3]), 8, 7)
    # group 0: one tile, group 1: none, group 2: three, group 3: one;
    # the two spare tiles repeat the last used tile's group
    assert tg.tolist() == [0, 2, 2, 2, 3, 3, 3] and nu.tolist() == [5]
    tg, nu = gm.tile_groups(jnp.zeros((4,), jnp.int32), 8, 3)
    assert nu.tolist() == [0]              # nothing to compute


def test_row_and_column_tiles():
    assert [gm.row_tile(m) for m in (0.5, 2, 9, 513, 10**6)] == \
        [8, 8, 16, 256, 256]
    assert gm.col_tile(2304, 896) == 896          # Mellum2: 8.26 MB fits
    assert gm.col_tile(7168, 2048) == 256         # one band of 7.3 MB
    assert gm.col_tile(70000, 256) == 0           # no band fits


@pytest.mark.parametrize("sizes,tm", [([5, 0, 17, 3], 8),
                                      ([0, 0, 40, 1], 16),
                                      ([0, 0, 0, 0], 8)])
@pytest.mark.parametrize("transpose", [False, True])
def test_gmm_matches_ragged_dot(sizes, tm, transpose):
    K, N = 128, 256
    lhs, sz = _layout(sizes, tm, K)
    rhs = jax.random.normal(jax.random.PRNGKey(1), (4, K, N))
    tg, nu = gm.tile_groups(sz, tm, lhs.shape[0] // tm)
    w = jnp.swapaxes(rhs, 1, 2) if transpose else rhs
    got = gm.gmm(lhs, w, tg, nu, tm=tm, transpose_rhs=transpose,
                 interpret=True)
    want = gm.gmm_ref(_bf16(lhs), _bf16(rhs), sz, tm=tm)
    used = int(nu[0]) * tm                 # rows past are not written
    np.testing.assert_allclose(np.asarray(got[:used]),
                               np.asarray(want[:used]), rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("sizes,tm", [([5, 0, 17, 3], 8), ([9, 9, 9, 9], 8)])
def test_tgmm_is_the_weight_gradient(sizes, tm):
    K, N = 128, 256
    lhs, sz = _layout(sizes, tm, K, seed=2)
    dout, _ = _layout(sizes, tm, N, seed=3)
    tg, nu = gm.tile_groups(sz, tm, lhs.shape[0] // tm)
    got = gm.tgmm(lhs, dout, sz, tg, nu, tm=tm, interpret=True)
    _, vjp = jax.vjp(lambda w: gm.gmm_ref(_bf16(lhs), w, sz, tm=tm),
                     jnp.zeros((4, K, N)))
    (want,) = vjp(_bf16(dout))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-4)
    empty = np.asarray([g for g, s in enumerate(sizes) if not s], np.int32)
    assert not np.asarray(got)[empty].any()


def test_dispatched_gradients_through_the_kernel_route(monkeypatch):
    """Value and gradients of a three-product expert block through the
    kernels' custom VJP equal the plain route's, rows the kernels leave
    unwritten never reaching a result."""
    sizes, tm, D, F = [6, 0, 11, 2], 8, 128, 256
    x, sz = _layout(sizes, tm, D, seed=4)
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    w = [jax.random.normal(ks[0], (4, D, F)) * 0.1,
         jax.random.normal(ks[1], (4, D, F)) * 0.1,
         jax.random.normal(ks[2], (4, F, D)) * 0.1]
    rows = int(sum(-(-s // tm) for s in sizes)) * tm

    def block(x, wg, wu, wd):
        h = dispatch.grouped_matmul(x, wg, sz, tm=tm)
        u = dispatch.grouped_matmul(x, wu, sz, tm=tm)
        y = dispatch.grouped_matmul(jax.nn.silu(h) * u, wd, sz, tm=tm)
        return jnp.sum(jnp.sin(y[:rows]))

    grad = jax.value_and_grad(block, argnums=(0, 1, 2, 3))
    monkeypatch.setenv("REPRO_KERNEL_MODE", "ref")
    want, g_want = grad(_bf16(x), *map(_bf16, w))
    monkeypatch.setenv("REPRO_KERNEL_MODE", "interpret")
    got, g_got = grad(x, *w)
    assert dispatch.routes_taken()["grouped_matmul"].get("pallas_interpret")
    # bfloat16 rounding of the intermediate products (the kernel rounds
    # h, u and their gradients again): relative to each array's scale
    assert abs(float(got) - float(want)) < 2e-2 * abs(float(want)) + 1e-2
    for a, b in zip(g_got, g_want):
        a, b = np.asarray(a), np.asarray(b)
        if a.shape == x.shape:
            a, b = a[:rows], b[:rows]
        scale = np.abs(b).max()
        assert np.abs(a - b).max() <= 3e-2 * scale + 1e-6
    assert np.isfinite(np.asarray(g_got[0][:rows])).all()


def test_kernel_route_refuses_a_weight_no_band_fits(monkeypatch):
    """On the kernel route a weight with no column band inside the VMEM
    budget fails loudly instead of taking another path."""
    monkeypatch.setenv("REPRO_KERNEL_MODE", "interpret")
    sz = jnp.asarray([8, 0], jnp.int32)
    x = jax.ShapeDtypeStruct((16, 70000), jnp.float32)
    w = jax.ShapeDtypeStruct((2, 70000, 256), jnp.float32)
    with pytest.raises(ValueError, match="VMEM budget"):
        jax.eval_shape(lambda x, w: dispatch.grouped_matmul(x, w, sz, tm=8),
                       x, w)
    monkeypatch.setenv("REPRO_KERNEL_MODE", "ref")
    out = jax.eval_shape(lambda x, w: dispatch.grouped_matmul(x, w, sz, tm=8),
                         x, w)
    assert out.shape == (16, 256)
