"""JAX's persistent compilation cache for the entry points.

``serve.main``, the benchmark mains and ``chip_smoke.py`` call
``use_compile_cache()`` once at start-up (never at import). Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing is
set here. Otherwise the cache lives in ``.jax_cache/`` at the root of the
checkout: a fixed path, so a second run of the same program loads the
megasteps the first one compiled instead of compiling them again.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
