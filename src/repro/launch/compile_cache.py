"""JAX's persistent compilation cache for the repo's entry points.

``JAX_COMPILATION_CACHE_DIR``, when set, is the cache: JAX reads it at
start-up and nothing here overrides it.  Otherwise the cache lives at the
fixed ``.jax_cache/`` at the root of the checkout (gitignored).  A fixed
path matters: the directory is part of what a later process looks up, so a
temporary or per-run directory would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    # the sweep/stream graphs compile in well under JAX's 1 s default
    # threshold on small shapes; cache them too
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.05)
    return path
