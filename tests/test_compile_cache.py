"""Placement of the persistent compilation cache (repro.launch.compile_cache)."""
from __future__ import annotations

import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro.launch.compile_cache import ENV_VAR, enable_compile_cache

CHECKOUT = Path(__file__).resolve().parents[1]


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)
    compilation_cache.reset_cache()


def test_env_var_is_left_to_jax(monkeypatch, tmp_path, restore_cache_dir):
    monkeypatch.setenv(ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_fixed_dir_in_checkout(monkeypatch, restore_cache_dir):
    monkeypatch.delenv(ENV_VAR, raising=False)
    path = enable_compile_cache()
    assert path == str(CHECKOUT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert enable_compile_cache() == path
    ignored = (CHECKOUT / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored


@pytest.mark.parametrize("placed", [True, False], ids=["env", "checkout"])
def test_entries_land_only_in_the_chosen_dir(tmp_path, placed):
    """A process writes its compiled programs where the variable points, or
    without it under its own checkout's ``.jax_cache`` — nowhere else: not
    under HOME, and not in another checkout."""
    # a second checkout holding only the helper, so that the default case
    # writes under tmp_path and not into this repository
    other = tmp_path / "checkout"
    launch = other / "src" / "repro" / "launch"
    launch.mkdir(parents=True)
    for pkg in (launch.parent, launch):
        (pkg / "__init__.py").write_text("")
    module = CHECKOUT / "src" / "repro" / "launch" / "compile_cache.py"
    (launch / "compile_cache.py").write_text(module.read_text())
    home = tmp_path / "home"
    home.mkdir()
    had_checkout_dir = (CHECKOUT / ".jax_cache").exists()
    env = {k: v for k, v in os.environ.items() if k != ENV_VAR}
    env.update({"HOME": str(home), "JAX_PLATFORMS": "cpu",
                "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
                "PYTHONPATH": str(other / "src")})
    cache = tmp_path / "cache" if placed else other / ".jax_cache"
    if placed:
        env[ENV_VAR] = str(cache)
    code = (
        "import jax, jax.numpy as jnp\n"
        "from repro.launch.compile_cache import enable_compile_cache\n"
        "print(enable_compile_cache())\n"
        "jax.jit(lambda x: jnp.sin(x) @ x.T)(jnp.ones((8, 8))).block_until_ready()\n"
    )
    run = subprocess.run([sys.executable, "-c", code], env=env, cwd=tmp_path,
                         check=True, timeout=120, capture_output=True, text=True)
    assert run.stdout.split() == [str(cache)]
    assert any(cache.iterdir())
    assert not any(home.iterdir())
    assert sorted(p.name for p in tmp_path.iterdir()) == sorted(
        ["checkout", "home"] + (["cache"] if placed else [])
    )
    if placed:
        assert not (other / ".jax_cache").exists()
    assert (CHECKOUT / ".jax_cache").exists() == had_checkout_dir
