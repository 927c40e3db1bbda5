"""The benchmark's weights and plain reference against the program, at
the smoke size on the CPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, smoke, spec as bspec, weights as bw

SEEDS = (0, 2**31 + 12345)


@pytest.fixture(scope="module")
def cell():
    return smoke.smoke_cell("sc2-3b.decode-long")


@pytest.fixture(scope="module")
def ref(cell):
    return bspec.reference_module(cell.config["reference"], cell.root)


def _leaves(tree):
    return dict(jax.tree_util.tree_flatten_with_path(tree)[0])


@pytest.mark.parametrize("seed", SEEDS)
def test_reference_init_draws_as_the_program(cell, ref, seed):
    from repro.models import init_params
    key = jax.random.PRNGKey(bw.program_seed(seed))
    mine = ref.init(cell.config, key)
    prog = init_params(harness.program_config(cell), key, jnp.float32)
    assert jax.tree.structure(mine) == jax.tree.structure(prog)
    for (path, a), b in zip(_leaves(mine).items(), jax.tree.leaves(prog)):
        assert np.array_equal(np.asarray(a), np.asarray(b)), path


@pytest.mark.parametrize("seed", SEEDS)
def test_weight_maker_changes_only_the_head(cell, ref, seed):
    from repro.models import init_params
    spec = cell.config
    params, _ = bw.make(ref, spec, seed, prompt_len=12)
    prog = init_params(harness.program_config(cell),
                       jax.random.PRNGKey(bw.program_seed(seed)), jnp.float32)
    for path, a in _leaves(params).items():
        if jax.tree_util.keystr(path) == "['lm_head']":
            continue
        # one jitted call fuses the scale into the draw: the same numbers
        # to within a unit in the last place of the eager draws
        np.testing.assert_allclose(np.asarray(a), np.asarray(_leaves(prog)[path]),
                                   rtol=3e-7, atol=1e-12, err_msg=str(path))
    w = spec["weights"]
    head, drawn = np.asarray(params["lm_head"]), np.asarray(prog["lm_head"])
    assert not head[:, w["zeroed_ids"]].any()
    a, b = w["answer_ids"]
    assert np.array_equal(head[:, a], head[:, b]) and head[:, a].any()
    rest = np.setdiff1d(np.arange(spec["vocab"]),
                        w["zeroed_ids"] + w["answer_ids"])
    np.testing.assert_allclose(head[:, rest], w["head_scale"] * drawn[:, rest],
                               rtol=3e-7, atol=1e-12)


def _batch(spec, seed, B=4, T=24):
    rng = np.random.default_rng(seed)
    tokens = rng.integers(3, spec["vocab"], (B, T)).astype(np.int32)
    mask = np.zeros((B, T), np.float32)
    mask[:, 12:] = 1.0
    return {"tokens": tokens,
            "behavior_logp": (-rng.random((B, T)) * 6).astype(np.float32),
            "advantages": (rng.standard_normal((B, 1)) * mask)
            .astype(np.float32),
            "mask": mask}


def test_reference_logits_match_the_program(cell, ref):
    from repro.models import forward_train
    spec = cell.config
    params, _ = bw.make(ref, spec, 3, prompt_len=12)
    tokens = jnp.asarray(_batch(spec, 3)["tokens"])
    prog, _ = forward_train(params, harness.program_config(cell),
                            {"tokens": tokens})
    np.testing.assert_allclose(np.asarray(ref.logits(params, spec, tokens)),
                               np.asarray(prog), rtol=2e-4, atol=2e-4)


def test_reference_loss_and_gradient_match_the_program(cell, ref):
    from repro.train.trainstep import make_loss_fn
    spec = cell.config
    params, _ = bw.make(ref, spec, 4, prompt_len=12)
    batch = {k: jnp.asarray(v) for k, v in _batch(spec, 4).items()}
    (loss, _), g_prog = jax.value_and_grad(
        make_loss_fn(harness.program_config(cell), rho=4.0),
        has_aux=True)(params, batch)
    denom = float(jnp.sum(batch["mask"][:, 1:]))
    (total, _), g_ref = jax.value_and_grad(
        lambda p: ref.aipo_loss_sum(p, spec, batch, rho=4.0),
        has_aux=True)(params)
    assert abs(float(total) / denom - float(loss)) < 1e-5
    for a, b in zip(jax.tree.leaves(g_ref), jax.tree.leaves(g_prog)):
        np.testing.assert_allclose(np.asarray(a) / denom, np.asarray(b),
                                   rtol=1e-3, atol=1e-7)
