"""Expert-parallel MoE: the explicit shard_map split of the held experts
over the 'model' axis must be numerically identical to the gathered
layer, tally included (multi-device subprocess exercises the real
shard_map collectives)."""
import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from repro import configs
from repro.models import init_params, forward_train
from repro.launch.mesh import make_mesh
from repro.models.sharding import activation_sharding

mesh = make_mesh((2, 4), ("data", "model"))
cfg = configs.get_smoke("deepseek-v3-671b")
p = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
toks = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, cfg.vocab)
base, aux = forward_train(p, cfg, {"tokens": toks})
for mode in ("ep_shmap",):
    with activation_sharding(mesh):
        got, got_aux = jax.jit(lambda pp, t: forward_train(
            pp, cfg.replace(moe_mode=mode), {"tokens": t}))(p, toks)
    err = float(jnp.max(jnp.abs(base - got)))
    assert err < 1e-4, (mode, err)
    # every pair is computed once; an expert is hit once per shard that
    # routes to it (each shard reads the expert's weights once)
    assert float(aux["moe_pairs_held"]) == float(got_aux["moe_pairs_held"])
    hit, got_hit = float(aux["moe_experts_hit"]), float(got_aux["moe_experts_hit"])
    assert hit <= got_hit <= 2 * hit, (hit, got_hit)
    print(mode, "ok", err)
"""


def test_ep_modes_match_gathered_multidevice():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    out = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                         capture_output=True, text=True, timeout=500,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))))
    assert out.returncode == 0, out.stderr[-2000:]
    assert "ep_shmap ok" in out.stdout
