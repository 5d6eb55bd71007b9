"""Where JAX keeps its persistent compilation cache.

One helper for the entry points that run on the chip (``chip_smoke.py``,
``benchmarks/run.py``).  It is never called at library import: importing
:mod:`repro` leaves JAX's configuration alone.
"""

from __future__ import annotations

import os

import jax

__all__ = ["ENV_VAR", "REPO_CACHE_DIR", "enable_compile_cache"]

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

# <repo>/.jax_cache — a fixed path: the directory is part of the cache's
# key, so a path built from a temporary name, a pid or the time never hits
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    If ``$JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and no
    other directory is set.  Otherwise the cache goes to
    ``<repo>/.jax_cache``.
    """
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
