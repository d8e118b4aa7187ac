"""JAX's persistent compilation cache, placed from outside or at a fixed path.

Every process that compiles for the chip calls ``enable()`` before its
first compile: ``chip_smoke.py``, each figure process of
``benchmarks/run.py`` and ``benchmarks/bench_fleet.py``, and the
examples. Tests do not: they compile for the CPU.
"""
from __future__ import annotations

import os
from pathlib import Path

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the cache: JAX reads it
    itself and no other directory is set here. Otherwise the cache is
    ``<repo>/.jax_cache`` — a fixed path, never a temporary one, since
    a later run finds an entry only where an earlier run wrote it."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
