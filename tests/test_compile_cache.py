"""The persistent compile cache is placed from outside the program:
``$JAX_COMPILATION_CACHE_DIR`` when set, else one fixed directory in
the checkout."""
from __future__ import annotations

import os
import subprocess
import sys
from pathlib import Path

import jax

from repro.launch import compile_cache

REPO = Path(__file__).resolve().parents[1]


def test_env_dir_holds_the_cache(tmp_path):
    cache = tmp_path / "cache"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(cache),
               PYTHONPATH=str(REPO / "src"))
    code = (
        "import jax, jax.numpy as jnp\n"
        "from repro.launch.compile_cache import enable_compile_cache\n"
        "print(enable_compile_cache(), jax.config.jax_compilation_cache_dir)\n"
        "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)\n"
        "jax.jit(lambda x: x * 2 + 1)(jnp.ones(3)).block_until_ready()\n")
    out = subprocess.run([sys.executable, "-c", code], cwd=tmp_path, env=env,
                         capture_output=True, text=True, timeout=120,
                         check=True).stdout.split()
    assert out == [str(cache), str(cache)]
    assert any(p.name.endswith("-cache") for p in cache.iterdir())


def test_default_dir_is_fixed_inside_the_checkout(monkeypatch):
    was = jax.config.jax_compilation_cache_dir
    assert compile_cache.DEFAULT_DIR == REPO / ".jax_cache"
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        assert compile_cache.enable_compile_cache() == \
            str(compile_cache.DEFAULT_DIR)
        assert jax.config.jax_compilation_cache_dir == \
            str(compile_cache.DEFAULT_DIR)
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
