"""JAX's persistent compilation cache for the command-line entry points.

``python chip_smoke.py``, ``benchmarks/run.py`` and the examples call
:func:`enable_compile_cache` first thing, so a second run of the same
program on the same platform loads its executables instead of compiling
them again.  ``import repro`` does not call it: the tests stay uncached.

The path is part of the cache's key, so it never moves: it is
``$JAX_COMPILATION_CACHE_DIR`` when that is set (JAX reads the variable
itself and nothing here overrides it), and otherwise ``.jax_cache/`` at
the root of the checkout (git-ignored).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
