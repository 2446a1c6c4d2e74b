"""JAX's persistent compilation cache for the repo's entry points.

Every engine configuration is its own jit, and a cold compile of one
engine program at Graph500 scale takes tens of seconds, so the entry
points (``chip_smoke.py``, ``examples/graph_analytics.py``,
``python -m repro.serve``, ``python -m benchmarks.run``) keep compiled
programs on disk between processes.
"""
from __future__ import annotations

import os

import jax

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_compilation_cache")


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing else is set here.  Otherwise the cache goes to the fixed
    ``<checkout>/.jax_compilation_cache`` (the directory the tests use):
    a path that moved between runs would never be hit again.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path
