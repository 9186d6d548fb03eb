"""Persistent XLA compilation cache for the entry points.

Entry points call :func:`enable_compile_cache` from ``main()``; importing
this module changes nothing. Where ``JAX_COMPILATION_CACHE_DIR`` is set,
JAX has already read it and this module sets no other directory. Where it
is not, the cache goes to ``<checkout>/.jax_cache``: a fixed path, since
the directory is part of what a later run must find again.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["DEFAULT_CACHE_DIR", "enable_compile_cache"]

# src/repro/launch/compile_cache.py -> the checkout root
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
