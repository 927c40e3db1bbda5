"""Parity, gradient and intermediate-size tests for the kernel-dispatch
layer (`repro.kernels.dispatch`): every backend (streamed-jnp, Pallas
interpret) must agree with the dense oracles in forward AND backward, and no
streamed path may materialize a full-vocab fp32 log-softmax."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import aipo
from repro.kernels import dispatch, ops, ref

BACKEND_MODES = ["ref", "interpret"]          # jnp-stream vs pallas-interpret


@pytest.fixture(params=BACKEND_MODES)
def kernel_mode(request, monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_MODE", request.param)
    return request.param


def _naive_logprob(logits, tokens):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return jnp.take_along_axis(logp, tokens[..., None], axis=-1)[..., 0]


# --------------------------------------------------- token_logprob parity ---

@pytest.mark.parametrize("T,V,bv", [(33, 257, 64), (64, 512, 128),
                                    (16, 4096, 512)])
def test_token_logprob_fwd_bwd_parity(T, V, bv, kernel_mode, rng):
    logits = jax.random.normal(rng, (T, V)) * 4
    toks = jax.random.randint(jax.random.PRNGKey(1), (T,), 0, V)
    w = jax.random.normal(jax.random.PRNGKey(2), (T,))

    got = dispatch.token_logprob(logits, toks, block_v=bv)
    want = _naive_logprob(logits, toks)
    assert jnp.max(jnp.abs(got - want)) < 1e-5

    g = jax.grad(
        lambda l: jnp.sum(dispatch.token_logprob(l, toks, block_v=bv) * w)
    )(logits)
    g_ref = jax.grad(
        lambda l: jnp.sum(_naive_logprob(l, toks) * w))(logits)
    assert jnp.max(jnp.abs(g - g_ref)) < 1e-5


def test_token_logprob_extreme_rows(kernel_mode, rng):
    """Duplicate-max rows and +-1e30 extreme logits (acceptance: <= 1e-5)."""
    logits = jax.random.normal(rng, (8, 128))
    logits = logits.at[0, 5].set(1e30)        # one dominating logit
    logits = logits.at[1, :].set(-1e30)       # uniformly tiny row
    logits = logits.at[2, 3].set(7.0).at[2, 99].set(7.0)   # duplicate max
    toks = jnp.arange(8) * 3
    got = dispatch.token_logprob(logits, toks, block_v=32)
    want = _naive_logprob(logits, toks)
    assert jnp.max(jnp.abs(got - want)) < 1e-5
    g = jax.grad(
        lambda l: dispatch.token_logprob(l, toks, block_v=32).sum())(logits)
    g_ref = jax.grad(lambda l: _naive_logprob(l, toks).sum())(logits)
    assert jnp.max(jnp.abs(g - g_ref)) < 1e-5


def test_token_logprob_batched_bf16(kernel_mode, rng):
    """[B, T, V] bf16 path (the trainer's actual layout); grad keeps dtype."""
    logits = (jax.random.normal(rng, (2, 17, 300)) * 4).astype(jnp.bfloat16)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 17), 0, 300)
    got = dispatch.token_logprob(logits, toks, block_v=64)
    want = _naive_logprob(logits, toks)
    assert got.dtype == jnp.float32
    assert jnp.max(jnp.abs(got - want)) < 3e-2
    g = jax.grad(lambda l: dispatch.token_logprob(l, toks, block_v=64).sum()
                 )(logits)
    assert g.dtype == jnp.bfloat16


def test_aipo_token_logprobs_routes_through_dispatch(kernel_mode, rng):
    """The trainer-loss entry point is the dispatch layer (same numbers)."""
    logits = jax.random.normal(rng, (2, 9, 97)) * 3
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 9), 0, 97)
    assert jnp.max(jnp.abs(aipo.token_logprobs(logits, toks)
                           - _naive_logprob(logits, toks))) < 1e-5


# -------------------------------------------------------- sampling parity ---

@pytest.mark.parametrize("temperature", [0.0, 0.7, 1.0])
def test_fused_sample_matches_reference(temperature, kernel_mode, rng):
    """Identical tokens + logprobs vs the dense Gumbel-max oracle under the
    same key (the counter-based noise is tile-shape invariant)."""
    logits = jax.random.normal(rng, (16, 515)) * 2
    key = jax.random.PRNGKey(42)
    tok_ref, lp_ref = ref.fused_sample_ref(logits, key, temperature)
    tok, lp = dispatch.sample(logits, key, temperature, block_v=64)
    assert jnp.array_equal(tok, tok_ref)
    assert jnp.max(jnp.abs(lp - lp_ref)) < 1e-5


@pytest.mark.parametrize("temperature", [0.0, 0.7, 1.0])
def test_fused_sample_pallas_wrapper(temperature, rng):
    """ops.fused_sample (always-Pallas jit wrapper) agrees with the oracle."""
    logits = jax.random.normal(rng, (8, 300)) * 2
    key = jax.random.PRNGKey(7)
    tok_ref, lp_ref = ref.fused_sample_ref(logits, key, temperature)
    tok, lp = ops.fused_sample(logits, key, temperature=temperature,
                               block_b=4, block_v=128)
    assert jnp.array_equal(tok, tok_ref)
    assert jnp.max(jnp.abs(lp - lp_ref)) < 1e-5


def test_sample_greedy_is_argmax(kernel_mode, rng):
    logits = jax.random.normal(rng, (6, 77))
    tok, lp = dispatch.sample(logits, jax.random.PRNGKey(0), 0.0, block_v=32)
    assert jnp.array_equal(tok, jnp.argmax(logits, axis=-1))
    want = _naive_logprob(logits, jnp.argmax(logits, axis=-1))
    assert jnp.max(jnp.abs(lp - want)) < 1e-5


def test_sample_distribution_matches_softmax(rng):
    """Empirical frequencies of the hash-Gumbel draw track softmax probs."""
    base = jnp.array([2.0, 1.0, 0.0, -1.0, 0.5, 1.5, -0.5, 0.0])
    n = 4096
    logits = jnp.broadcast_to(base, (n, 8))    # independent noise per row
    tok, _ = dispatch.sample(logits, jax.random.PRNGKey(3), 1.0)
    freq = np.bincount(np.asarray(tok), minlength=8) / n
    probs = np.asarray(jax.nn.softmax(base))
    assert np.max(np.abs(freq - probs)) < 0.05


def test_gumbel_noise_no_counter_wrap():
    """Rows 2^32/V apart must NOT share noise: a linear row*V+col counter
    wraps in uint32 at the paper's V=256k (row 0 == row 16384)."""
    from repro.kernels.fused_sample import gumbel_noise
    V = 262144
    cols = jnp.arange(64)
    k0 = k1 = jnp.uint32(7)
    rows_a = jnp.zeros((64,), jnp.int32)
    rows_b = jnp.full((64,), (1 << 32) // V, jnp.int32)
    na = gumbel_noise(rows_a, cols, k0, k1)
    nb = gumbel_noise(rows_b, cols, k0, k1)
    assert not jnp.array_equal(na, nb)


@pytest.mark.parametrize("temperature", [0.7, 1.0])
def test_hash_noise_tokens_bit_identical_across_backends(temperature, rng):
    """hash_uniform's 24 random bits reach float32 through int32 (Mosaic
    casts int32 -> f32, not uint32 -> f32): the Pallas body
    (interpreted), the scan fallback and the dense oracle still draw the
    same tokens, and the uniforms equal the direct uint32 -> float32
    conversion bit for bit."""
    from repro.kernels.fused_sample import _mix, fused_sample, hash_uniform
    logits = jax.random.normal(rng, (24, 1000)) * 2
    key = jax.random.PRNGKey(11)
    dense_tok, dense_lp = ref.fused_sample_ref(logits, key, temperature)
    scan_tok, scan_lp = dispatch._sample_stream_jnp(logits, key, temperature,
                                                    128)
    ker_tok, ker_lp = fused_sample(logits, key, temperature=temperature,
                                   block_b=8, block_v=128, interpret=True)
    assert jnp.array_equal(scan_tok, dense_tok)
    assert jnp.array_equal(ker_tok, dense_tok)
    assert jnp.max(jnp.abs(scan_lp - dense_lp)) < 1e-5
    assert jnp.max(jnp.abs(ker_lp - dense_lp)) < 1e-5

    rows = jnp.arange(64)[:, None] * jnp.ones((1, 512), jnp.int32)
    cols = jnp.arange(512)[None, :] * jnp.ones((64, 1), jnp.int32)
    k0, k1 = jnp.uint32(0xDEADBEEF), jnp.uint32(0x12345678)
    x = _mix(rows.astype(jnp.uint32) * jnp.uint32(0x9E3779B9) + k0)
    x = _mix(x + cols.astype(jnp.uint32) * jnp.uint32(0x85EBCA6B) + k1)
    direct = ((x >> jnp.uint32(8)).astype(jnp.float32) + 0.5) \
        * (1.0 / (1 << 24))
    assert jnp.array_equal(hash_uniform(rows, cols, k0, k1), direct)


def test_route_compiles_kernels_only_on_tpu(monkeypatch):
    """auto picks the compiled kernels on a TPU above the size threshold,
    the streamed jnp path elsewhere; each choice is recorded."""
    monkeypatch.delenv("REPRO_KERNEL_MODE", raising=False)
    monkeypatch.delenv("REPRO_PALLAS_COMPILE", raising=False)
    monkeypatch.delenv("REPRO_KERNEL_MIN_VOCAB", raising=False)

    def route(n, dtype=jnp.float32):
        return dispatch._route("probe", n, dtype, "REPRO_KERNEL_MIN_VOCAB",
                               4096)

    before = dispatch.routes_taken().get("probe", {})
    assert jax.default_backend() == "cpu"
    assert route(49152) == "jnp"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert route(49152) == "pallas_compile"
    assert route(1024) == "jnp"                  # below the threshold
    assert route(49152, jnp.int32) == "jnp"      # no kernel for the dtype
    monkeypatch.setenv("REPRO_KERNEL_MODE", "interpret")
    assert route(49152) == "pallas_interpret"
    after = dispatch.routes_taken()["probe"]
    assert after.get("pallas_compile", 0) - before.get("pallas_compile",
                                                       0) == 1
    assert after["jnp"] - before.get("jnp", 0) == 3


def test_sample_keys_decorrelate(rng):
    logits = jax.random.normal(rng, (64, 128)) * 0.1   # near-uniform
    t1, _ = dispatch.sample(logits, jax.random.PRNGKey(0), 1.0)
    t2, _ = dispatch.sample(logits, jax.random.PRNGKey(1), 1.0)
    assert not jnp.array_equal(t1, t2)


# -------------------------------------------------------- attention parity ---

@pytest.mark.parametrize("S", [128, 100])     # divisible + padded
def test_attention_dispatch_parity_and_grad(S, rng, monkeypatch):
    from repro.models.attention import chunked_attention
    monkeypatch.setenv("REPRO_KERNEL_MODE", "interpret")
    monkeypatch.setenv("REPRO_ATTN_BLOCK", "32")
    ks = jax.random.split(rng, 3)
    q = jax.random.normal(ks[0], (2, S, 8, 32)) * 0.5
    k = jax.random.normal(ks[1], (2, S, 2, 32)) * 0.5
    v = jax.random.normal(ks[2], (2, S, 2, 32))
    got = dispatch.attention(q, k, v, causal=True)
    want = chunked_attention(q, k, v, causal=True, block_q=64)
    assert jnp.max(jnp.abs(got - want)) < 1e-4

    def loss_d(q_):
        return dispatch.attention(q_, k, v, causal=True).sum()

    def loss_c(q_):
        return chunked_attention(q_, k, v, causal=True, block_q=64).sum()

    assert jnp.max(jnp.abs(jax.grad(loss_d)(q) - jax.grad(loss_c)(q))) < 1e-4


def test_attention_dispatch_fallbacks(rng, monkeypatch):
    """Windowed / cross / asymmetric-dim segments use the chunked path even
    when the mode asks for Pallas (the kernel does not implement them)."""
    from repro.models.attention import chunked_attention
    monkeypatch.setenv("REPRO_KERNEL_MODE", "interpret")
    ks = jax.random.split(rng, 3)
    q = jax.random.normal(ks[0], (1, 64, 4, 16))
    k = jax.random.normal(ks[1], (1, 64, 2, 16))
    v = jax.random.normal(ks[2], (1, 64, 2, 16))
    cases = [dict(causal=True, window=8), dict(causal=False),
             dict(causal=True, q_offset=32)]
    for kw in cases:
        got = dispatch.attention(q, k, v, **kw)
        want = chunked_attention(q, k, v, **kw)
        assert jnp.max(jnp.abs(got - want)) < 1e-5


# ---------------------------------------------- intermediate-size asserts ---
# jaxpr accounting lives in tools.analysis.jaxpr_budget (shared with the
# `python -m tools.analysis` hot-path gate); conftest puts the repo root
# on sys.path
from tools.analysis.jaxpr_budget import (count_big_intermediates,  # noqa: E402
                                         float_eqn_sizes)


@pytest.mark.parametrize("fn_name", ["logprob", "sample"])
def test_no_full_vocab_materialization_forward(fn_name, kernel_mode, rng):
    """Acceptance check: with V >> block_v, no float intermediate anywhere in
    the forward jaxpr (including scan/pallas bodies) reaches rows * V --
    i.e. the streamed paths never build a full-vocab fp32 log-softmax."""
    T, V, bv = 32, 4096, 512
    logits = jax.random.normal(rng, (T, V))
    if fn_name == "logprob":
        toks = jax.random.randint(jax.random.PRNGKey(1), (T,), 0, V)
        jx = jax.make_jaxpr(
            lambda l: dispatch.token_logprob(l, toks, block_v=bv))(logits)
    else:
        jx = jax.make_jaxpr(
            lambda l: dispatch.sample(l, jax.random.PRNGKey(0), 1.0,
                                      block_v=bv))(logits)
    big = [s for s in float_eqn_sizes(jx.jaxpr) if s >= T * V]
    assert not big, f"full-vocab float intermediates in {fn_name}: {big}"


def test_grad_materializes_less_than_naive(kernel_mode, rng):
    """The custom-VJP grad path holds at most the unavoidable dlogits-sized
    buffers; the naive log-softmax grad holds strictly more."""
    T, V, bv = 32, 4096, 512
    logits = jax.random.normal(rng, (T, V))
    toks = jax.random.randint(jax.random.PRNGKey(1), (T,), 0, V)
    jx_s = jax.make_jaxpr(jax.grad(
        lambda l: dispatch.token_logprob(l, toks, block_v=bv).sum()))(logits)
    jx_n = jax.make_jaxpr(jax.grad(
        lambda l: _naive_logprob(l, toks).sum()))(logits)
    big_s = count_big_intermediates(jx_s.jaxpr, T * V)
    big_n = count_big_intermediates(jx_n.jaxpr, T * V)
    # zeros-init + scan output + the in-body carry write (XLA aliases the
    # latter two); the naive grad shows ~14 full-vocab intermediates here
    assert big_s <= 3
    assert big_s < big_n
