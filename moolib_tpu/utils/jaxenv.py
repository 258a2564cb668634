"""Persistent XLA compile cache placement for entry points.

Every ``main`` that compiles (``chip_smoke.py``, ``bench*.py``,
``__graft_entry__.py``, the examples' CLIs) calls
:func:`enable_compile_cache` before its first jit; library functions never
do. jax stays lazily imported so control-plane-only processes never load
XLA.
"""

from __future__ import annotations

import os

__all__ = ["enable_compile_cache"]

# The cache key includes the directory, so a path that moves (tempfile, pid,
# timestamp) never hits: one fixed, git-ignored directory per checkout.
_REPO_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable_compile_cache() -> str:
    """Turn on jax's persistent compilation cache; returns the directory.

    ``JAX_COMPILATION_CACHE_DIR`` wins: jax reads it by itself, so nothing
    is set in code and whoever placed the variable (a machine image, a CI
    job) finds the cache where they put it. Otherwise the cache lives at
    ``<checkout>/.jax_cache``, the same path for every process and run of
    this checkout."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", _REPO_CACHE)
    return _REPO_CACHE
