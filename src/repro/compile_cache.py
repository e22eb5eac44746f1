"""Placement of JAX's persistent compilation cache.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``) call
:func:`setup_compile_cache` once at start-up, before their first compile.
Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already uses that
directory and nothing here names another one. Otherwise the cache lives
at one fixed path inside the checkout, ``<repo>/.jax_cache`` (listed in
``.gitignore``), so a later run of the same checkout finds its entries.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def setup_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    # keep every executable: each kernel composition compiles in about a
    # second, under JAX's default one-second floor for caching
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
