"""JAX's persistent compilation cache, placed where the next run finds it.

Entry points (``chip_smoke.py``, ``launch/train_svm.py``,
``launch/path_server.py``, ``benchmarks/bench_screening.py``) call
:func:`enable_compile_cache` once at start-up; nothing turns the cache on at
import time. The cache key includes the directory, so the directory must
not move between runs: ``JAX_COMPILATION_CACHE_DIR`` wins when it is set
(JAX reads it itself, and no other directory is set in code); otherwise the
cache lives in ``.jax_cache/`` at the root of the checkout.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: the in-checkout default (listed in .gitignore)
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
