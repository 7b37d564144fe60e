"""JAX's persistent compilation cache for this repo's entry points.

Every entry point (``chip_smoke.py``, ``examples/*.py``,
``benchmarks/run.py``) calls :func:`enable_compile_cache` before its first
compile, so a second run of the same program loads its executables instead
of compiling them again. Importing this module changes nothing.
"""
from __future__ import annotations

import os

import jax

# <checkout>/.jax_cache: a fixed path, because the cache key includes it
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    this sets nothing; otherwise the cache lives in ``.jax_cache`` at the
    root of the checkout."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
