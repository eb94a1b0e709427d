"""Start-up helpers shared by the entry points: compile cache and device facts.

Nothing here runs on import; entry points call these from their `__main__`
block, so tests that import or call the launchers never touch the cache.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

# <checkout>/.jax_cache, found from this file (src/repro/launch/runtime.py)
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Keep compiled programs across processes; returns the cache directory.

    Where `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself and nothing
    is set here. Otherwise the cache goes to a fixed directory in the checkout.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)


def device_info() -> dict:
    """The device the program runs on, as JAX reports it."""
    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices())}
