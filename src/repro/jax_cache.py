"""Persistent JAX compilation cache for the program's entry points.

Compiling the level steps and kernels is set-up time. A persistent cache
lets the next process on the same checkout load those programs instead of
compiling them again. The entry points (``repro.cli``, ``chip_smoke.py``,
the benchmarks' ``__main__``) call ``configure_compile_cache()`` once before
their first compile; importing the library never does.

The cache path is part of the cache's key, so it is fixed: the directory the
``JAX_COMPILATION_CACHE_DIR`` variable names, or else ``.jax_cache`` at the
root of the checkout (git-ignored).
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed directory and
    return that directory."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        CHECKOUT_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
