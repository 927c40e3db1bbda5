"""Mellum2's expert model in the plain reference (``references/moe.py``)
against the program, at a small size on the CPU: the same parameters,
the train forward's logits, B=1 prefill then paged decode through the
engine's functions, the train step's loss and gradients, and YaRN's
tables against their closed form.

Every width is cut, nothing else: 4 layers (3 sliding, 1 full with YaRN),
16 experts of which the program holds 8 (experts 0-7) and each token
picks 4, a window of 8 so that the rows run past it.  The CPU computes
float32 products exactly as the reference's ``HIGHEST``, so the
tolerances bound accumulation order only; the bfloat16 reference (the
control) fails each of them.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import spec as bspec
from bench import weights as bw

ref = bspec.reference_module("moe")

#: float32 logits through 4 layers of different accumulation order
#: (sorted expert rows, chunked attention, streamed vocab): about 1e-5
#: apart; bfloat16 products put them 1e-2 and more apart
LOGIT_TOL = 2e-4


def small(experts_held=8, n_experts=16, top_k=4):
    """(program config, reference spec) of a small Mellum2."""
    from repro import configs
    from repro.configs import MoEConfig
    cfg = configs.get_config("mellum2-12b-a2.5b").replace(
        n_layers=4, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
        d_ff=32, vocab=128, window=8, max_seq=1024,
        moe=MoEConfig(n_experts=n_experts, top_k=top_k, router="softmax",
                      norm_topk=True, aux_loss_coef=0.0),
        experts_held=experts_held).validate()
    spec = dict(bspec.load_config("mellum2-12b-a2.5b.ep8-l4"),
                n_layers=4, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
                d_ff=32, vocab=128, window=8, n_experts=n_experts,
                num_experts_per_tok=top_k, experts_held=experts_held)
    assert spec["layer_types"] == ["sliding_attention"] * 3 \
        + ["full_attention"]
    return cfg, spec


@pytest.fixture(scope="module")
def pair():
    return small()


@pytest.fixture(scope="module")
def params(pair):
    _, spec = pair
    p, _ = bw.make(ref, spec, 3, prompt_len=12)
    return p


def _tokens(seed, B, T, vocab=128):
    return jnp.asarray(np.random.default_rng(seed).integers(
        3, vocab, (B, T)), jnp.int32)


@pytest.mark.parametrize("seed", [0, 2**31 + 12345])
def test_reference_init_draws_as_the_program(pair, seed):
    """The reference draws every leaf as the program does; the
    benchmark's weights rescale only the embedding, to the
    configuration's ``weights.embed_std``."""
    from repro.models import init_params
    cfg, spec = pair
    key = jax.random.PRNGKey(bw.program_seed(seed))
    drawn = dict(spec["weights"])
    std = drawn.pop("embed_std")
    mine = ref.init(dict(spec, weights=drawn), key)
    prog = init_params(cfg, key, jnp.float32)
    assert jax.tree.structure(mine) == jax.tree.structure(prog)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(mine)[0],
                            jax.tree.leaves(prog)):
        assert np.array_equal(np.asarray(a), np.asarray(b)), path
    bench = ref.init(spec, key)
    np.testing.assert_allclose(
        np.asarray(bench["embed"]),
        np.asarray(prog["embed"]) * std * np.sqrt(spec["vocab"]), rtol=1e-6)
    assert abs(float(jnp.std(bench["embed"])) - std) < 0.05 * std
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(bench)[0],
                            jax.tree.leaves(prog)):
        if "embed" not in jax.tree_util.keystr(path):
            assert np.array_equal(np.asarray(a), np.asarray(b)), path


def test_train_forward_logits_match_the_reference(pair, params):
    from repro.models import forward_train
    cfg, spec = pair
    tokens = _tokens(1, 2, 40)            # past the window of 8
    prog, aux = forward_train(params, cfg, {"tokens": tokens})
    want = ref.logits(params, spec, tokens)
    np.testing.assert_allclose(np.asarray(prog), np.asarray(want),
                               rtol=LOGIT_TOL, atol=LOGIT_TOL)
    control = ref.logits(params, spec, tokens, jnp.bfloat16)
    assert float(jnp.max(jnp.abs(control.astype(jnp.float32) - want))) \
        > 10 * LOGIT_TOL
    # every held pair was computed and counted
    assert 0 < float(aux["moe_pairs_held"]) <= 2 * 40 * 4 * 4


def test_prefill_then_paged_decode_match_the_reference(pair, params):
    """The engine's B=1 prefill into a paged slot, then paged decode of
    each next token, give the logits of the reference's full forward at
    every position, past the window on the sliding layers."""
    from repro.models import decode_step
    from repro.models.paging import paged_blocks
    from repro.rl.rollout import admit_row_paged, start_row_pool
    cfg, spec = pair
    Sp, T, P = 12, 40, 4
    toks = _tokens(2, 1, T)
    want = ref.logits(params, spec, toks)[0]
    control = ref.logits(params, spec, toks, jnp.bfloat16)[0]
    state = start_row_pool(cfg, 2, T, Sp, kv_layout="paged",
                           kv_page_size=P)
    mb = paged_blocks(T, P)
    pages = jnp.asarray(list(range(mb)) + [2 * mb], jnp.int32)
    state = admit_row_paged(params, cfg, state, toks[:, :Sp], pages, 1,
                            n_cached=0)
    got = [state.last_logits[1]]
    cache = state.cache
    step = jax.jit(decode_step, static_argnums=1)
    for t in range(Sp, T - 1):
        row = jnp.stack([jnp.int32(0), toks[0, t]])[:, None]
        logits, cache = step(params, cfg, cache, row)
        got.append(logits[1])
    got = jnp.stack(got)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want[Sp - 1:T - 1]),
                               rtol=LOGIT_TOL, atol=LOGIT_TOL)
    assert float(jnp.max(jnp.abs(control[Sp - 1:T - 1].astype(jnp.float32)
                                 - want[Sp - 1:T - 1]))) > 10 * LOGIT_TOL
    # the cache tallied the prefill and each decode step
    assert int(cache["moe"][2]) == cfg.n_layers * (1 + T - 1 - Sp)


def _batch(seed, B=4, T=24, vocab=128):
    rng = np.random.default_rng(seed)
    mask = np.zeros((B, T), np.float32)
    mask[:, 12:] = 1.0
    return {"tokens": _tokens(seed, B, T, vocab),
            "behavior_logp": jnp.asarray(-rng.random((B, T)) * 6, jnp.float32),
            "advantages": jnp.asarray(rng.standard_normal((B, 1)) * mask,
                                      jnp.float32),
            "mask": jnp.asarray(mask)}


def test_train_step_loss_and_gradients_match_the_reference(pair, params):
    """The AIPO loss and every leaf's gradient, routing included (the
    router's gradient flows through the renormalized top-k weights)."""
    from repro.train.trainstep import make_loss_fn
    cfg, spec = pair
    batch = _batch(4)
    (loss, _), g_prog = jax.jit(jax.value_and_grad(
        make_loss_fn(cfg, rho=4.0), has_aux=True))(params, batch)
    denom = float(jnp.sum(batch["mask"][:, 1:]))

    def ref_grads(dtype):
        return jax.jit(jax.value_and_grad(
            lambda p: ref.aipo_loss_sum(p, spec, batch, rho=4.0,
                                        dtype=dtype), has_aux=True))(params)
    (total, _), g_ref = ref_grads(jnp.float32)
    # the loss: a mean of about 300 float32 terms of size 1
    assert abs(float(total) / denom - float(loss)) < 1e-5
    # gradients: float32 sums over positions and pairs in another
    # order; relative to each leaf's largest entry
    worst = 0.0
    for a, b in zip(jax.tree.leaves(g_ref), jax.tree.leaves(g_prog)):
        a = np.asarray(a) / denom
        np.testing.assert_allclose(a, np.asarray(b), rtol=1e-3,
                                   atol=1e-3 * np.abs(a).max() + 1e-9)
    (_, _), g_ctl = ref_grads(jnp.bfloat16)
    for a, b in zip(jax.tree.leaves(g_ref), jax.tree.leaves(g_ctl)):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        worst = max(worst, np.abs(a - b).max() / (np.abs(a).max() + 1e-30))
    assert worst > 1e-3


def test_yarn_tables_match_the_closed_form():
    """Mellum2's full-layer table at its published numbers (head 128,
    theta 5e5, factor 16 over 8,192 positions, betas 32 and 1): pairs
    below 18 keep theta^(-2i/128), pairs above 35 take it over 16, the
    18 between ramp linearly; cos and sin carry 0.1 ln 16 + 1."""
    from repro import configs
    from repro.models.common import apply_rope, rope_freqs, yarn_freqs
    cfg = configs.get_config("mellum2-12b-a2.5b")
    y, hd, theta = cfg.yarn, cfg.hd, cfg.rope_theta
    base = theta ** (-np.arange(0, hd, 2) / hd)
    # floor(128 ln(8192 / 64 pi) / 2 ln 5e5) = 18; ceil(... / 2 pi) = 35
    lo, hi = 18, 35
    ramp = np.clip((np.arange(hd // 2) - lo) / (hi - lo), 0, 1)
    closed = base * (1 - ramp) + base / 16 * ramp
    np.testing.assert_allclose(yarn_freqs(hd, theta, y), closed, rtol=1e-12)
    np.testing.assert_allclose(yarn_freqs(hd, theta, y)[:lo], base[:lo],
                               rtol=1e-12)
    np.testing.assert_allclose(yarn_freqs(hd, theta, y)[hi:], base[hi:] / 16,
                               rtol=1e-12)
    np.testing.assert_allclose(rope_freqs(hd, theta), base, rtol=1e-12)
    assert abs(y.attention_factor - (0.1 * np.log(16) + 1)) < 1e-12
    rp = bspec.load_config("mellum2-12b-a2.5b.ep8-l4")["rope_parameters"]
    inv, scale = ref.yarn_inv_freq(hd, theta, rp["full_attention"])
    np.testing.assert_allclose(inv, closed, rtol=1e-12)
    assert scale == y.attention_factor
    # the rotation itself: at position p, pair i turns by p * freq_i and
    # the pair's length grows by the attention factor
    x = jnp.zeros((1, 1, 1, hd)).at[..., 0].set(1.0).at[..., hd // 2 - 1]\
        .set(1.0)
    out = apply_rope(x, jnp.asarray([[100]]), theta, y)[0, 0, 0]
    f = y.attention_factor
    np.testing.assert_allclose(
        [out[0], out[hd // 2], out[hd // 2 - 1], out[hd - 1]],
        [f * np.cos(100 * closed[0]), f * np.sin(100 * closed[0]),
         f * np.cos(100 * closed[-1]), f * np.sin(100 * closed[-1])],
        rtol=1e-5, atol=1e-6)


def test_layer_types_pick_the_tables():
    """Layers 3, 7, ..., 27 of the published model are full and take
    YaRN; the others slide and take plain RoPE; StarCoder2 has none."""
    from repro import configs
    from repro.models.backbone import layer_yarn
    cfg = configs.get_config("mellum2-12b-a2.5b")
    full = [i for i in range(cfg.n_layers) if layer_yarn(cfg, i) is not None]
    assert full == list(range(3, 28, 4))
    spec = bspec.load_config("mellum2-12b-a2.5b.ep8-l4")
    assert [t == "full_attention" for t in spec["layer_types"]] == \
        [layer_yarn(cfg, i) is not None for i in range(4)]
    sc2 = configs.get_config("starcoder2-3b")
    assert all(layer_yarn(sc2, i) is None for i in range(sc2.n_layers))


def test_registry_gives_the_published_sizes():
    """Mellum2's registry entry at its published sizes (12.15 B
    parameters), and the cell's cut of it (340.3 M) as the file states."""
    from repro import configs
    from bench import harness
    cfg = configs.get_config("mellum2-12b-a2.5b")
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
            cfg.hd, cfg.d_ff, cfg.vocab) == (28, 2304, 32, 4, 128, 896, 98304)
    assert (cfg.moe.n_experts, cfg.moe.top_k, cfg.held_experts) == (64, 8, 64)
    assert configs.param_count(cfg) == (12_149_784_576, 2_438_922_240)
    cell = bspec.load_cell("mellum2.decode-long")
    cut = harness.program_config(cell)
    assert configs.param_count(cut)[0] == 340_328_448
    assert (cut.n_layers, cut.vocab, cut.held_experts) == (4, 12288, 8)
