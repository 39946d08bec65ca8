"""JAX's persistent compilation cache, placed from outside the program.

Call ``enable_compile_cache()`` at the start of an entry point (never at
import).  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself
and nothing is set here.  Otherwise the cache goes to the fixed
``<checkout>/.jax_cache`` (listed in ``.gitignore``): the directory is part
of the cache's key, so it never depends on a temporary name, a process id
or the time.

The cache's key covers the programs' op metadata too (named scopes,
source lines).  By default JAX strips it from the key, so a program whose
scopes changed would load an executable compiled before the change, and
a device trace of it would name its ops by the old scopes.  Source files
are named relative to the checkout, so that two checkouts of one commit
at different paths still share their entries.
"""
from __future__ import annotations

import os
import re
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    import jax

    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      "^" + re.escape(f"{CHECKOUT}{os.sep}"))
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
