"""Persistent compilation cache for every launcher and ``chip_smoke.py``.

A cold process on the chip recompiles every step program (a 22-layer serving
step takes tens of seconds). With the cache on, a later process in the same
checkout loads what an earlier one compiled.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# src/repro/launch/compile_cache.py -> the checkout's root
CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and no
    other directory is set here. Otherwise the cache is ``<checkout>/.jax_cache``:
    a fixed path, because a directory that moves between runs never hits.
    Call it before the first compile; it touches no device.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
