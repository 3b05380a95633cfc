"""JAX's persistent compilation cache, placed from outside the program.

Call :func:`enable_compile_cache` at the top of an entry point's ``main()``
(never at import). If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps
its cache there and nothing is set in code. Otherwise the cache goes to the
fixed directory ``<repo>/.jax_cache``: the path is part of the cache's key, so
it is never derived from a temporary name, a pid or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns the directory in use."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    return str(REPO_CACHE)
