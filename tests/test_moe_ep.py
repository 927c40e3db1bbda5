"""Expert-parallel MoE paths: constraint-EP and explicit shard_map EP must
be numerically identical to the gathered baseline (multi-device subprocess
exercises the real shard_map collectives)."""
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
base, _ = forward_train(p, cfg, {"tokens": toks})
for mode in ("ep", "ep_shmap"):
    with activation_sharding(mesh):
        got = jax.jit(lambda pp, t: forward_train(
            pp, cfg.replace(moe_mode=mode), {"tokens": t})[0])(p, toks)
    err = float(jnp.max(jnp.abs(base - got)))
    assert err < 1e-4, (mode, err)
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
    assert "ep ok" in out.stdout and "ep_shmap ok" in out.stdout
