"""Persistent XLA compilation cache for every entry point of the package.

A fresh process deserializes the compiled supersteps and scoring programs
instead of compiling them again.  The cache lives where
``JAX_COMPILATION_CACHE_DIR`` says — jax reads that variable itself, so no
other directory is set in code — and otherwise at the fixed
``<checkout>/.jax_cache``: the path is part of what makes a later process
find the entries, so it never depends on a temporary name, a pid or the
time.  The minimum-compile-time threshold is zeroed so every program is
cached: this package's programs are few and heavily reused.
"""
from __future__ import annotations

import os
import pathlib
from typing import Optional

import jax

CHECKOUT_CACHE = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"

_initialized: Optional[str] = None


def cache_dir() -> str:
    """The directory the cache uses in this process."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CHECKOUT_CACHE)


def init() -> str:
    """Turn the cache on (once per process); returns its directory."""
    global _initialized
    if _initialized is None:
        path = cache_dir()
        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            jax.config.update("jax_compilation_cache_dir", path)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        _initialized = path
    return _initialized
