"""Where an entry point keeps JAX's persistent compilation cache.

Call ``enable_compile_cache()`` once at the start of a program —
``chip_smoke.py``, the launchers, the benchmarks — never at library
import and never in tests. A compiled program is keyed by, among other
things, the cache's path, so the directory is fixed: the checkout's own
``.jax_cache`` (listed in ``.gitignore``), derived from this file's
location and from no temporary name, pid or time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    this sets nothing; otherwise the cache goes to the checkout's
    ``.jax_cache``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
