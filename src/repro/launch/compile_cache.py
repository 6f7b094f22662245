"""Where JAX's persistent compilation cache lives.

Entry points call ``enable_compile_cache()`` at the start of ``main`` —
never at import, so tests and library callers keep JAX's defaults (no
cache).  ``JAX_COMPILATION_CACHE_DIR``, when set, wins and JAX reads it
itself; otherwise the cache goes to ``<repo>/.jax_cache``.  The path is
fixed because it is part of the cache key: a directory named from a
pid, a temp name or the time would never hit.
"""
from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["REPO_CACHE_DIR", "enable_compile_cache"]

REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use.

    The cache key keeps the ops' metadata, whose ``op_name`` carries the
    program's layer scopes (``repro.tracing``): by default JAX drops it
    from the key and would serve a program cached before a scope existed
    in the scoped one's place, and a trace would not show the scope.
    """
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    outside = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if outside:
        return outside
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
