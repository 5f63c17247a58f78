"""JAX's persistent compilation cache, at a place that does not move.

The cache key includes the cache path, so a directory that changes from
run to run never hits.  ``JAX_COMPILATION_CACHE_DIR``, when set, places the
cache from outside; otherwise it lives at ``<repo>/.cache/jax``.
"""
from __future__ import annotations

import os
import pathlib

DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[2] / ".cache" / "jax"


def enable_compile_cache() -> str:
    """Turn the cache on; returns its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX reads the variable itself
    and nothing is set here."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
