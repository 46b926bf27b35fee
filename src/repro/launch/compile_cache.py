"""Where JAX keeps its persistent compilation cache.

:func:`enable` is the first call of every program entry point
(``chip_smoke.py``, ``repro.launch.serve``, ``benchmarks/run.py``), made
before anything compiles.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
reads it itself and nothing is set here.  Otherwise the cache goes to the
fixed ``<repo>/.jax_cache`` (git-ignored), so every run from one checkout
finds what earlier runs compiled.  Tests never call it: they run with the
cache off.
"""

from __future__ import annotations

import os
import pathlib

import jax

DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
