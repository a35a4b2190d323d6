"""JAX's persistent compilation cache for this repository's entry points.

Called once at the start of each entry point (``repro.launch.serve``,
``chip_smoke.py``, ``benchmarks/run.py``), never at import.
"""
from __future__ import annotations

import os
import pathlib

import jax

#: the checkout's own cache: a fixed path, so every run of this checkout
#: (and every process of one run) finds what an earlier one compiled
CHECKOUT_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def setup_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing else is configured here. Otherwise the cache lives in the
    checkout's ``.jax_cache`` directory (gitignored)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
