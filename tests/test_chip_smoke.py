"""``chip_smoke.py`` rehearsed on the CPU at the smoke config: every
phase runs through the launcher's entry points, and the script still
fails -- with no result line -- because there is no TPU."""
import os
import subprocess
import sys

import jax
import pytest

from repro.launch import train

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("chips,phases", [
    (1, ("default", "paged")),
    (4, ("colocated", "split")),
])
def test_chip_smoke_rehearsal_runs_phases_and_reports_nothing(
        tmp_path, chips, phases):
    cache = tmp_path / "jax_cache"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(cache),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={chips}")
    env.pop("REPRO_KERNEL_MODE", None)
    out = subprocess.run(
        [sys.executable, "chip_smoke.py", "--smoke", "--chips", str(chips)],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    for name in phases:
        assert f"phase {name}: ok" in out.stdout, out.stdout[-3000:] + \
            out.stderr[-3000:]
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
    assert f"compilation cache: {cache}" in out.stdout
    assert any(cache.iterdir()), "nothing written to the compilation cache"


def test_compile_cache_defaults_to_repo_dir(monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    assert train.enable_compile_cache() == "/elsewhere"
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    before = jax.config.jax_compilation_cache_dir
    try:
        path = train.enable_compile_cache()
        assert path == os.path.join(REPO, ".jax_cache")
        assert os.environ["JAX_COMPILATION_CACHE_DIR"] == path  # children
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
