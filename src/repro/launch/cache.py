"""JAX's persistent compilation cache, placed from outside.

A compiled program is keyed partly on the cache directory, so a directory
that moves between runs never hits.  :func:`use_compile_cache` therefore
uses ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it (JAX reads
it itself; nothing else is configured) and otherwise the fixed
``<repo>/.jax_cache`` — never a temporary, per-process or time-stamped
path.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache for every program this
    process compiles from now on (each one, however short its compile);
    returns the directory in use."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(REPO_CACHE)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
