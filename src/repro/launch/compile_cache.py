"""JAX's persistent compilation cache, placed from outside the program.

Entry points call :func:`enable_compile_cache` once, before their first
compile; importing this module changes nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: The in-checkout default (git-ignored). Fixed, never temp/pid/time
#: derived: a cache whose directory moves between runs never hits.
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Return the directory compiled programs are cached in.

    With ``$JAX_COMPILATION_CACHE_DIR`` set, JAX already reads it at
    start-up and the cache stays exactly there. Otherwise the cache goes
    to :data:`DEFAULT_DIR`.
    """
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
