"""Where JAX keeps its persistent compilation cache.

A chip run compiles HPL, the train step and every kernel from cold unless a
cache from an earlier run is found, and the cache key includes the cache's
path — so the path must not move between runs. :func:`use_compile_cache`
is called on the start-up path of every entry point (``chip_smoke.py``,
``repro.launch.train``, ``repro.launch.serve``, ``benchmarks.run``).
"""
from __future__ import annotations

import os
from pathlib import Path

# <checkout>/.jax_cache — fixed, listed in .gitignore
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Returns the cache directory in use. When ``JAX_COMPILATION_CACHE_DIR``
    is set, JAX reads it itself and nothing is set here; otherwise the cache
    goes to :data:`DEFAULT_DIR`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
