"""JAX's persistent compilation cache, placed at a fixed path.

A chip run compiles every plan it executes; with the cache on, a second
process that runs the same plans loads them instead.  The cache key
includes the cache directory, so the directory must not move between
runs: it is ``$JAX_COMPILATION_CACHE_DIR`` when that is set (JAX reads
the variable itself), else ``<checkout>/.jax_cache``.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``, the examples)
call :func:`enable_compile_cache` once at start-up; the library itself
never turns the cache on.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Tuple

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
#: the checkout holding ``src/repro``
CHECKOUT = Path(__file__).resolve().parents[2]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def resolve_cache_dir() -> Tuple[str, bool]:
    """``(directory, set_in_code)``: the environment's directory (JAX
    picks it up, nothing is set in code), or the fixed in-checkout one,
    which :func:`enable_compile_cache` sets."""
    env = os.environ.get(CACHE_ENV)
    if env:
        return env, False
    return str(DEFAULT_DIR), True


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.

    Every compilation is cached, however short: a Pallas kernel
    compiles in about a second, under JAX's default threshold."""
    import jax
    path, set_in_code = resolve_cache_dir()
    if set_in_code:
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
