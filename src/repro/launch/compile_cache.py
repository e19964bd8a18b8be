"""Where JAX keeps its persistent compilation cache.

A cold process on the chip spends minutes compiling a full-width serving
step; the persistent cache turns every later start on the same machine
into a read. The cache key includes the directory, so the directory must
not move between runs: it is never made from a temporary name, a process
id or the time.
"""
from __future__ import annotations

import os

import jax

#: Fixed in-checkout cache directory (listed in ``.gitignore``).
CACHE_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, already placed it (JAX reads
    the variable itself) and nothing here overrides it; otherwise the
    cache goes to :data:`CACHE_DIR`. Call from an entry point, never at
    import.
    """
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
