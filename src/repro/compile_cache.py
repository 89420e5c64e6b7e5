"""JAX's persistent compilation cache, kept at one fixed place.

A cache entry is keyed by the program and by where the cache lives, so a
directory that moves between runs never hits.  :func:`enable_compile_cache`
is called by ``chip_smoke.py`` and the benchmark entry points before their
first compile.
"""
from __future__ import annotations

import os
from pathlib import Path

__all__ = ["REPO_CACHE_DIR", "enable_compile_cache"]

#: the checkout's own cache directory (listed in ``.gitignore``)
REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
    other path is set here.  Otherwise the cache goes to
    :data:`REPO_CACHE_DIR` inside the checkout."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    REPO_CACHE_DIR.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
