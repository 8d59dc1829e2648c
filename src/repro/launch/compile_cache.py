"""JAX persistent compilation cache for the launchers.

Every process that drives the engine compiles the same plans again; the
persistent cache lets a later process on the same machine load them
instead. ``enable_compile_cache`` is called by the entry points
(``python -m repro.launch.serve``, ``chip_smoke.py``) before their first
compile — never at import time.

The directory is ``$JAX_COMPILATION_CACHE_DIR`` when that is set, and
otherwise the fixed ``<checkout>/.jax_cache`` (git-ignored). The path is
part of what makes a cache entry findable again, so it is never built
from a temporary name, a process id or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# src/repro/launch/compile_cache.py -> the checkout root
CHECKOUT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
