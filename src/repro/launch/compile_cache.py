"""JAX's persistent compilation cache for the entry points.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads that variable
itself and ``enable_compile_cache`` changes nothing. Otherwise the cache
goes to ``.jax_cache/`` at the root of the checkout: a fixed path, so a
second run of any entry point from the same checkout finds what the
first one compiled. Library code never calls this; entry points
(``chip_smoke.py``, ``python -m repro.launch.render_service``,
``python -m benchmarks.run``) do, before their first compile.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CACHE_DIR", "enable_compile_cache"]

# src/repro/launch/compile_cache.py -> the checkout root is three up
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
