"""DeepSeek-LLM-67B's layer in the plain reference: parity with the
program at the smoke size, and the tensor-parallel share.  Under TP-8
each chip holds one kv head with its query heads and an eighth of the FFN
columns; what the eight shares give, summed (the all-reduce), is what
the uncut layer gives."""
import jax
import jax.numpy as jnp
import numpy as np

from bench import spec as bspec

SHARES = 8


def _spec(cfg):
    keys = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
            "d_ff", "vocab", "act", "norm", "bias", "rope_theta", "window",
            "tie_embeddings")
    return {k: getattr(cfg, k) for k in keys}


def _smoke():
    from repro.configs.deepseek_67b import smoke
    return smoke()


def test_reference_logits_match_the_program():
    from repro.models import forward_train, init_params
    cfg = _smoke()
    spec = _spec(cfg)
    assert (spec["act"], spec["norm"]) == ("silu_gated", "rmsnorm")
    ref = bspec.reference_module("dense")
    key = jax.random.PRNGKey(11)
    params = ref.init(spec, key)
    prog_params = init_params(cfg, key, jnp.float32)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(prog_params)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    tokens = jax.random.randint(jax.random.PRNGKey(12), (2, 40), 3,
                                spec["vocab"])
    prog, _ = forward_train(params, cfg, {"tokens": tokens})
    np.testing.assert_allclose(np.asarray(ref.logits(params, spec, tokens)),
                               np.asarray(prog), rtol=2e-4, atol=2e-4)


def _share(layer, spec, s):
    """Chip ``s``'s share of one layer: kv head ``s`` with its query
    heads, and the ``s``-th eighth of the FFN columns."""
    hd, G = spec["head_dim"], spec["n_heads"] // spec["n_kv_heads"]
    F = spec["d_ff"] // SHARES
    q = slice(s * G * hd, (s + 1) * G * hd)
    kv = slice(s * hd, (s + 1) * hd)
    f = slice(s * F, (s + 1) * F)
    a, m = layer["attn"], layer["mlp"]
    attn = {"wq": a["wq"][:, q], "wk": a["wk"][:, kv], "wv": a["wv"][:, kv],
            "wo": a["wo"][q]}
    mlp = {"w_gate": m["w_gate"][:, f], "w_up": m["w_up"][:, f],
           "w_down": m["w_down"][f]}
    share = dict(spec, n_heads=G, n_kv_heads=1, d_ff=F)
    return attn, mlp, share


def test_tp8_shares_sum_to_the_uncut_layer():
    ref = bspec.reference_module("dense")
    spec = dict(_spec(_smoke()), n_layers=1, d_model=128, n_heads=16,
                n_kv_heads=SHARES, head_dim=16, d_ff=256)
    params = ref.init(spec, jax.random.PRNGKey(3))
    layer = jax.tree.map(lambda x: x[0], params["layers"])
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 24, spec["d_model"]))
    with jax.default_matmul_precision("highest"):
        h = ref._norm(x, layer["ln1"], spec["norm"])
        attn = ref._attention(layer["attn"], h, spec, jnp.float32)
        g = ref._norm(x + attn, layer["ln2"], spec["norm"])
        mlp = ref._mlp(layer["mlp"], g, spec, jnp.float32)
        parts = [_share(layer, spec, s) for s in range(SHARES)]
        attn_sum = sum(ref._attention(a, h, sh, jnp.float32)
                       for a, _, sh in parts)
        mlp_sum = sum(ref._mlp(m, g, sh, jnp.float32) for _, m, sh in parts)
        one = ref._mlp(parts[0][1], g, parts[0][2], jnp.float32)
    np.testing.assert_allclose(np.asarray(attn_sum), np.asarray(attn),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(mlp_sum), np.asarray(mlp),
                               rtol=1e-5, atol=1e-5)
    # one share alone gives only its part: the sum is not trivial
    assert not np.allclose(np.asarray(one), np.asarray(mlp), atol=1e-3)
