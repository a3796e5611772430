"""Persistent XLA compilation cache for the repository's entry points.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``, ``examples/``)
call :func:`enable_compile_cache` once at start-up; library modules never
do, so importing the package changes no JAX configuration.
"""
from __future__ import annotations

import os

import jax

#: the cache directory when ``JAX_COMPILATION_CACHE_DIR`` is unset: a fixed
#: path inside the checkout (git-ignored). The path is part of each cache
#: key, so it must not move between runs.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here; otherwise the cache goes to
    :data:`DEFAULT_CACHE_DIR`."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
