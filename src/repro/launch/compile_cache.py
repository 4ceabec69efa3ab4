"""Where JAX keeps its persistent compilation cache for the entry points.

``python chip_smoke.py``, ``python -m repro.api.cli`` and
``python -m benchmarks.run`` call :func:`use_compile_cache` first thing;
nothing calls it at import, so a library user's own cache setting is
never overridden.  When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads
it itself and this sets nothing.  Otherwise the cache goes to
``<checkout>/.jax_cache`` (git-ignored): a fixed path, because the path
is part of what a later run looks entries up by.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
