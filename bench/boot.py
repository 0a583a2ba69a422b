"""Start-up shared by the benchmark's scripts: the compile cache and the chips.

Import this before JAX.  The compile cache lives in ``.jax_cache`` at the
checkout's root, a fixed path (the path is part of every entry's key),
and reaches the program through ``JAX_COMPILATION_CACHE_DIR``.
"""
from __future__ import annotations

import os
import sys

from bench.spec import ROOT

__all__ = ["tpus"]

CACHE_DIR = ROOT / ".jax_cache"


def tpus(chips: int, who: str):
    """The first ``chips`` TPU devices, with the compile cache on; None,
    with the reason on standard error, where JAX sees fewer."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    if str(ROOT / "src") not in sys.path:
        sys.path.insert(0, str(ROOT / "src"))
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"{who} needs {chips} TPU chip(s); JAX sees {len(devices)} "
              f"{devices[0].platform} device(s)", file=sys.stderr)
        return None
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    return devices[:chips]
