"""Placement of JAX's persistent compilation cache.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``, the serve CLI) call
``use_compile_cache()`` once before compiling anything; importing ``repro``
never touches the cache.  The cache directory is part of what makes an
entry hit, so it is fixed: ``JAX_COMPILATION_CACHE_DIR`` when the
environment sets it (JAX reads that variable itself, so nothing else is
set), otherwise ``<repo>/.jax_cache``.
"""
from __future__ import annotations

import os

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one fixed place and
    return that directory."""
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
