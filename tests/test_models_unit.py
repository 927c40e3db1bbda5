"""Unit + property tests for model components: attention masks/windows,
MoE routing invariants, Mamba2 vs naive recurrence, mLSTM vs step
recurrence, grouped scan equivalence, optimizer."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro import configs
from repro.models.attention import chunked_attention


# ----------------------------------------------------------- attention -----

def _naive_attention(q, k, v, window=0):
    B, S, H, hd = q.shape
    K = k.shape[2]
    g = H // K
    qf = q.reshape(B, S, K, g, hd).astype(jnp.float32)
    s = jnp.einsum("bqkgh,bskh->bkgqs", qf, k.astype(jnp.float32))
    s = s * hd ** -0.5
    rows = jnp.arange(S)[:, None]
    cols = jnp.arange(S)[None, :]
    mask = cols <= rows
    if window:
        mask &= cols > rows - window
    s = jnp.where(mask[None, None, None], s, -1e30)
    p = jax.nn.softmax(s, -1)
    o = jnp.einsum("bkgqs,bskh->bqkgh", p, v.astype(jnp.float32))
    return o.reshape(B, S, H, hd)


@pytest.mark.parametrize("S,window,bq", [
    (96, 0, 32), (96, 32, 16), (128, 64, 32), (100, 48, 32),
])
def test_chunked_attention_matches_naive(S, window, bq, rng):
    ks = jax.random.split(rng, 3)
    q = jax.random.normal(ks[0], (2, S, 4, 16)) * 0.5
    k = jax.random.normal(ks[1], (2, S, 2, 16)) * 0.5
    v = jax.random.normal(ks[2], (2, S, 2, 16))
    got = chunked_attention(q, k, v, window=window, block_q=bq)
    want = _naive_attention(q, k, v, window=window)
    assert jnp.max(jnp.abs(got - want)) < 1e-4


def test_windowed_attention_is_subquadratic_slice(rng):
    """The windowed path must dynamic-slice K/V (compute O(S*W)), which
    implies each query only sees ceil(W+bq) keys."""
    S, W, bq = 256, 32, 32
    q = jax.random.normal(rng, (1, S, 2, 8))
    k = jax.random.normal(jax.random.PRNGKey(1), (1, S, 2, 8))
    v = jax.random.normal(jax.random.PRNGKey(2), (1, S, 2, 8))
    got = chunked_attention(q, k, v, window=W, block_q=bq)
    want = _naive_attention(q, k, v, window=W)
    assert jnp.max(jnp.abs(got - want)) < 1e-4


# ----------------------------------------------------------------- MoE -----

def _moe_setup(S=64, E=4, k=2):
    cfg = configs.get_smoke("deepseek-v3-671b")
    from repro.models.ffn import moe_params
    p = moe_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, S, cfg.d_model)) * 0.3
    return cfg, p, x


def test_moe_output_finite_and_aux_positive():
    from repro.models.ffn import moe_forward
    cfg, p, x = _moe_setup()
    y, tally = moe_forward(p, x, cfg)
    assert y.shape == x.shape
    assert bool(jnp.all(jnp.isfinite(y)))
    assert float(tally[0]) > 0              # the load-balance term


def test_moe_topk_routing_invariants():
    """Each token routes to exactly top_k distinct experts with weights
    summing to 1 (sigmoid router normalization)."""
    from repro.models.ffn import _route
    cfg, p, x = _moe_setup()
    m = cfg.moe
    probs, weights, idx = _route(p["moe"] if "moe" in p else p, x, m)
    assert idx.shape[-1] == m.top_k
    # distinct experts per token
    srt = jnp.sort(idx, axis=-1)
    assert bool(jnp.all(srt[..., 1:] != srt[..., :-1]))
    assert jnp.allclose(jnp.sum(weights, -1), 1.0, atol=1e-5)


def test_moe_lossless_capacity_matches_dense_experts():
    """The dropless layer == the explicit per-token expert mixture
    computed naively."""
    from repro.models.ffn import moe_forward, _route
    cfg, p, x = _moe_setup(S=16)
    m = cfg.moe
    y, _ = moe_forward(p, x, cfg)
    probs, weights, idx = _route(p, x, m)

    def naive(xg, wg, ig):
        out = jnp.zeros_like(xg)
        for e in range(m.n_experts):
            h = jax.nn.silu(xg @ p["w_gate"][e]) * (xg @ p["w_up"][e])
            ye = h @ p["w_down"][e]
            sel = jnp.sum(jnp.where(ig == e, wg, 0.0), axis=-1)
            out = out + ye * sel[..., None]
        return out

    want = jax.vmap(naive)(x, weights, idx)
    if m.n_shared:
        from repro.models.ffn import mlp_forward
        want = want + mlp_forward(p["shared"], x, "silu_gated")
    assert jnp.max(jnp.abs(y - want)) < 1e-4


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 1000))
def test_moe_held_pairs_never_dropped(seed):
    """Whatever the routing -- all picks on one held expert, a mix of
    held and absent ones -- every (token, held expert) pair is computed
    and counted, and absent experts add nothing."""
    from repro.models.ffn import experts_held
    rng = np.random.default_rng(seed)
    T, D, F, E, G, first, k = 24, 16, 8, 8, 4, 2, 3
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    p = {"w_gate": jax.random.normal(ks[0], (G, D, F)),
         "w_up": jax.random.normal(ks[1], (G, D, F)),
         "w_down": jax.random.normal(ks[2], (G, F, D))}
    x = jnp.asarray(rng.normal(size=(T, D)), jnp.float32)
    hot = int(rng.integers(0, E))
    idx = np.stack([rng.permutation(E)[:k] for _ in range(T)])
    idx[: T // 2, 0] = hot                   # skew: half the tokens on one
    idx[: T // 2, 1:] = [e for e in range(E) if e != hot][:k - 1]
    w = jnp.asarray(rng.random((T, k)), jnp.float32)
    y, pairs, hit = experts_held(p, x, w, jnp.asarray(idx), first, E)
    want = np.zeros((T, D), np.float32)
    for t in range(T):
        for j, e in enumerate(idx[t]):
            if first <= e < first + G:
                g = e - first
                h = jax.nn.silu(x[t] @ p["w_gate"][g]) * (x[t] @ p["w_up"][g])
                want[t] += float(w[t, j]) * np.asarray(h @ p["w_down"][g])
    held = (idx >= first) & (idx < first + G)
    assert int(pairs) == int(held.sum())
    assert int(hit) == len(set(idx[held].tolist()))
    np.testing.assert_allclose(np.asarray(y), want, rtol=1e-4, atol=1e-3)


# ------------------------------------------------------------- Mamba2 ------

def _naive_mamba_scan(dt, A, xh, Bf, Cf):
    """Step-by-step SSD recurrence oracle."""
    B_, S, H, P = xh.shape
    N = Bf.shape[-1]
    h = np.zeros((B_, H, P, N))
    ys = []
    for t in range(S):
        dec = np.exp(dt[:, t] * A)[:, :, None, None]
        h = h * dec + np.einsum("bh,bhp,bn->bhpn", dt[:, t], xh[:, t],
                                Bf[:, t])
        ys.append(np.einsum("bhpn,bn->bhp", h, Cf[:, t]))
    return np.stack(ys, 1)


def test_mamba2_chunked_matches_stepwise(rng):
    cfg = configs.get_smoke("zamba2-7b")
    from repro.models.ssm import mamba2_params, mamba2_forward, \
        _mamba_dims, _split_in, _causal_conv
    p = mamba2_params(rng, cfg, jnp.float32)
    d_in, H, P, N = _mamba_dims(cfg)
    B_, S = 2, 48
    x = jax.random.normal(jax.random.PRNGKey(1), (B_, S, cfg.d_model)) * 0.3
    # reproduce the internal pre-processing, then compare scan cores
    z, xc, Bc, Cc, dt = _split_in(p, x, cfg)
    conv_out, _ = _causal_conv(jnp.concatenate([xc, Bc, Cc], -1), p["conv_w"])
    xc, Bc, Cc = jnp.split(conv_out, [d_in, d_in + N], -1)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    xh = xc.reshape(B_, S, H, P).astype(jnp.float32)
    want = _naive_mamba_scan(np.asarray(dt), np.asarray(A), np.asarray(xh),
                             np.asarray(Bc, dtype=np.float32),
                             np.asarray(Cc, dtype=np.float32))
    # full forward path (includes the same core + gate/norm/proj): instead
    # compare the decode path accumulated over time, which uses the
    # stepwise recurrence, against the chunked forward.
    from repro.models.ssm import mamba2_decode, mamba2_init_state
    y_full = mamba2_forward(p, x, cfg)
    st = mamba2_init_state(cfg, B_)
    ys = []
    for t in range(S):
        yt, st = mamba2_decode(p, x[:, t:t + 1], st, cfg)
        ys.append(yt)
    y_step = jnp.concatenate(ys, axis=1)
    assert jnp.max(jnp.abs(y_full - y_step)) < 1e-3


def test_mlstm_chunked_matches_stepwise(rng):
    cfg = configs.get_smoke("xlstm-350m")
    from repro.models.ssm import (mlstm_params, mlstm_forward, mlstm_decode,
                                  mlstm_init_state)
    p = mlstm_params(rng, cfg, jnp.float32)
    B_, S = 2, 40
    x = jax.random.normal(jax.random.PRNGKey(1), (B_, S, cfg.d_model)) * 0.3
    y_full, _ = mlstm_forward(p, x, cfg)
    st = mlstm_init_state(cfg, B_)
    ys = []
    for t in range(S):
        yt, st = mlstm_decode(p, x[:, t:t + 1], st, cfg)
        ys.append(yt)
    y_step = jnp.concatenate(ys, axis=1)
    assert jnp.max(jnp.abs(y_full - y_step)) < 1e-3


# ------------------------------------------------------------ optimizer ----

def test_adam_matches_reference(rng):
    """Our Adam == textbook Adam on a quadratic."""
    from repro.train.optimizer import adam_init, adam_update
    w = {"w": jnp.asarray([1.0, -2.0, 3.0])}
    st = adam_init(w)
    lr, b1, b2, eps = 1e-2, 0.9, 0.95, 1e-8
    m = np.zeros(3)
    v = np.zeros(3)
    wref = np.asarray([1.0, -2.0, 3.0])
    for t in range(1, 6):
        g = 2 * wref
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        wref = wref - lr * (m / (1 - b1 ** t)) / \
            (np.sqrt(v / (1 - b2 ** t)) + eps)
        grads = {"w": 2 * w["w"]}
        w, st, _ = adam_update(w, grads, st, lr=lr, b1=b1, b2=b2, eps=eps,
                               max_grad_norm=0.0)
    assert np.allclose(np.asarray(w["w"]), wref, atol=1e-5)


def test_grad_clip():
    from repro.train.optimizer import clip_by_global_norm
    g = {"a": jnp.ones((10,)) * 10}
    clipped, gn = clip_by_global_norm(g, 1.0)
    assert abs(float(jnp.linalg.norm(clipped["a"])) - 1.0) < 1e-5


# ------------------------------------------------------- grouped scans -----

@pytest.mark.parametrize("u", [1, 2, 3])
def test_grouped_scan_equivalence(u, rng):
    """scan_group must not change numerics (incl. tail handling)."""
    from repro.models import forward_train, init_params
    cfg = configs.get_smoke("deepseek-67b").replace(n_layers=2)
    p = init_params(cfg, rng, jnp.float32)
    b = {"tokens": jnp.ones((2, 32), jnp.int32)}
    base, _ = forward_train(p, cfg, b)
    got, _ = forward_train(p, cfg.replace(scan_group=u), b)
    assert jnp.max(jnp.abs(base - got)) < 1e-5
