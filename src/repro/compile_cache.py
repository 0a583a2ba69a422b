"""JAX's persistent compilation cache, kept at one fixed place.

Every entry point (``repro.launch.serve``, ``repro.launch.train``,
``benchmarks.run``, ``chip_smoke.py``) calls :func:`enable_compile_cache`
before its first compile.  Where ``JAX_COMPILATION_CACHE_DIR`` is set,
JAX reads that directory itself and this module sets no other.  Where it
is not, the cache lives in ``.jax_cache`` at the repository root: a
fixed path, because the path is part of every entry's key and a
directory that moves between runs never hits.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["ENV_VAR", "DEFAULT_DIR", "enable_compile_cache"]

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
