"""JAX's persistent compilation cache for the program's entry points.

A chip run compiles every kernel and jitted step cold unless an earlier
process left its executables on disk.  ``enable_compile_cache()`` is the
one place that decides where they go:

  * ``JAX_COMPILATION_CACHE_DIR`` set in the environment: JAX reads it
    itself, and this module names no other directory;
  * otherwise one fixed directory inside the checkout, ``<repo>/.jax_cache``
    (git-ignored).  The path is part of the cache key, so it never depends
    on a temp dir, a pid or the time.

Every compile is cached, however short: the ledger kernels compile in
well under JAX's default one-second floor, and a smoke run is made of
little else.
"""
from __future__ import annotations

import os
from pathlib import Path

#: the cache directory used when JAX_COMPILATION_CACHE_DIR is unset
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
