"""Where the entry points keep JAX's persistent compilation cache.

The cache key includes the directory, so it must not move between runs:
``JAX_COMPILATION_CACHE_DIR`` when the environment sets it (JAX reads that
variable itself), else one fixed directory inside the checkout.  Entry
points call :func:`enable_compile_cache` before their first compile;
importing this module changes nothing, and tests never call it.
"""
from __future__ import annotations

import os

import jax

#: the checkout root: this file is <root>/src/repro/launch/compile_cache.py
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))

#: the cache directory used when JAX_COMPILATION_CACHE_DIR is not set
#: (listed in .gitignore)
DEFAULT_CACHE_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
