"""The chip's share of Mellum2's expert layer, and routing that drops
nothing.

Share: under 8-way expert parallelism each chip holds 8 of the 64
experts, routes over all 64 and computes its own experts' part; what the
8 shares give, summed (the exchange), is what the uncut layer gives.
Dropless: a router that sends every token to the same 8 experts gives the
same log-probs at the generator's B=1 prefill of 12 tokens, in the
trainer's row of 513 and in the reference -- a capacity of ``S * k / E *
1.25`` slots per expert would keep 1 of the prefill's 12 tokens and 80 of
the row's 513.
"""
import jax
import jax.numpy as jnp
import numpy as np

from bench import spec as bspec
from bench.test_moe_reference import LOGIT_TOL, small

ref = bspec.reference_module("moe")
SHARES = 8


def test_eight_shares_sum_to_the_uncut_layer():
    _, full = small(experts_held=64, n_experts=64, top_k=8)
    key = jax.random.PRNGKey(5)
    layer = jax.tree.map(lambda a: a[0], ref.init(full, key)["moe_layers"])
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 24, full["d_model"]))
    want = ref.experts(layer["moe"], x, full)
    parts = []
    for s in range(SHARES):
        spec = dict(full, experts_held=8, expert_offset=8 * s)
        mine = jax.tree.map(lambda a: a[0],
                            ref.init(spec, key)["moe_layers"])["moe"]
        # a share's experts are the uncut layer's, drawn alike
        for name in ("w_gate", "w_up", "w_down"):
            assert np.array_equal(np.asarray(mine[name]),
                                  np.asarray(layer["moe"][name][8 * s:
                                                                8 * s + 8]))
        parts.append(ref.experts(mine, x, spec))
    np.testing.assert_allclose(np.asarray(sum(parts)), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    # one share alone gives only its part
    assert not np.allclose(np.asarray(parts[0]), np.asarray(want), atol=1e-3)


def test_program_share_is_the_reference_share():
    """The program's layer computing experts 24-31 of 64 gives the
    reference's share for the same tokens: its routing over all 64, its
    pairs, nothing from the absent 56; and the program's config holding
    8 draws the uncut layer's first 8."""
    from repro.models import init_params
    from repro.models.ffn import _route, experts_held
    cfg, spec = small(experts_held=0, n_experts=64, top_k=8)
    uncut = jax.tree.map(lambda a: a[0], init_params(
        cfg, jax.random.PRNGKey(7), jnp.float32)["moe_layers"])["moe"]
    held = init_params(cfg.replace(experts_held=8), jax.random.PRNGKey(7),
                       jnp.float32)["moe_layers"]["moe"]["w_up"][0]
    assert np.array_equal(np.asarray(held), np.asarray(uncut["w_up"][:8]))
    share = dict(uncut, **{k: uncut[k][24:32]
                           for k in ("w_gate", "w_up", "w_down")})
    x = jax.random.normal(jax.random.PRNGKey(8), (48, cfg.d_model))
    _, w, idx = _route(share, x, cfg.moe)
    y, pairs, hit = experts_held(share, x, w, idx, 24, 64)
    spec = dict(spec, experts_held=8, expert_offset=24)
    with jax.default_matmul_precision("highest"):
        want = ref.experts(share, x[None], spec)[0]
        gate = ref.route(share, x, spec)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=1e-4,
                               atol=1e-5)
    assert int(pairs) == int(jnp.sum(gate > 0))
    assert int(hit) == int(jnp.sum(jnp.any(gate > 0, axis=0)))


def _forced(params):
    """Router weights zero: every logit 0, so every token's top 8 are
    experts 0-7 (ties go to the lowest ids), each weighed 1/8."""
    layers = dict(params["moe_layers"])
    layers["moe"] = dict(layers["moe"],
                         w_router=jnp.zeros_like(layers["moe"]["w_router"]))
    return dict(params, moe_layers=layers)


def test_forced_router_drops_nothing():
    from repro.models import forward_train
    from repro.models.paging import paged_blocks
    from repro.rl.rollout import admit_row_paged, start_row_pool
    cfg, spec = small(experts_held=8, n_experts=64, top_k=8)
    params = _forced(ref.init(spec, jax.random.PRNGKey(9)))
    row = jnp.asarray(np.random.default_rng(10).integers(3, 128, (1, 513)),
                      jnp.int32)
    Sp = 12
    # trainer: one row of 513; every token's 8 picks are all held
    train, aux = forward_train(params, cfg, {"tokens": row})
    assert float(aux["moe_pairs_held"]) == 4 * 513 * 8
    assert float(aux["moe_experts_hit"]) == 4 * 8
    # generator: the B=1 prefill of the row's first 12 tokens
    state = start_row_pool(cfg, 1, 513, Sp, kv_layout="paged",
                           kv_page_size=16)
    mb = paged_blocks(513, 16)
    state = admit_row_paged(params, cfg, state, row[:, :Sp],
                            jnp.asarray(list(range(mb)) + [mb], jnp.int32),
                            0, n_cached=0)
    want = ref.logits(params, spec, row)[0]
    lp = lambda lg: jax.nn.log_softmax(lg, axis=-1)
    np.testing.assert_allclose(np.asarray(lp(state.last_logits[0])),
                               np.asarray(lp(want[Sp - 1])),
                               rtol=LOGIT_TOL, atol=LOGIT_TOL)
    np.testing.assert_allclose(np.asarray(lp(train[0])), np.asarray(lp(want)),
                               rtol=LOGIT_TOL, atol=LOGIT_TOL)
    # the reference routes as the program: experts 0-7 at 1/8 each
    x = jnp.ones((1, 3, cfg.d_model))
    gate = ref.route(jax.tree.map(lambda a: a[0],
                                  params["moe_layers"])["moe"], x, spec)
    np.testing.assert_allclose(np.asarray(gate), 1 / 8)
