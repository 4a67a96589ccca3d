"""JAX's persistent compilation cache for the entry points.

A run that finds its programs in the cache skips compiling them.  The
cache is keyed by its directory, so the directory must not move between
runs: ``JAX_COMPILATION_CACHE_DIR`` places it from outside (JAX reads the
variable itself), and otherwise it lives at ``.jax_cache/`` in the
checkout, found from this file's location rather than the working
directory.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on before the first compile; returns its
    directory."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return jax.config.jax_compilation_cache_dir
