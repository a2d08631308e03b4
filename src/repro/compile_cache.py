"""JAX's persistent compilation cache for the command-line entry points.

Only scripts call :func:`enable_compile_cache`, from their
``if __name__ == "__main__":`` block: importing the library or running the
tests never turns the cache on.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# A fixed path inside the checkout (listed in .gitignore): a cache that
# moved between runs would never hit.
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads that directory
    itself and no other is set here; otherwise the cache goes to
    :data:`DEFAULT_DIR`.  Every compilation is cached, however short, so a
    second run of the same command compiles nothing."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
