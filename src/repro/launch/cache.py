"""JAX's persistent compilation cache, placed from outside or at a fixed
path in the checkout.

Every entry point calls ``enable_compile_cache()`` before its first
compile.  With ``JAX_COMPILATION_CACHE_DIR`` set, JAX reads it itself and
no other directory is set here; otherwise the cache lives at
``<checkout>/.jax_cache`` (listed in .gitignore).  The path never depends
on a temp name, a pid or the time, so a later process on the same
checkout finds what an earlier one compiled.
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
