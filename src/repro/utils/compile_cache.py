"""JAX's persistent compilation cache, placed from outside the program.

Call ``enable_compile_cache()`` at the start of an entry point (never at
import).  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself
and nothing is set here.  Otherwise the cache goes to the fixed
``<checkout>/.jax_cache`` (listed in ``.gitignore``): the directory is part
of the cache's key, so it never depends on a temporary name, a process id
or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
