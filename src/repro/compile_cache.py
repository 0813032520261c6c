"""Placement of JAX's persistent compilation cache for entry points.

Scripts call :func:`enable_compile_cache` at the top of their ``main``;
the library never does, so importing :mod:`repro` leaves JAX's cache
settings alone.

- With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already reads it, and this
  sets nothing.
- Otherwise the cache goes to ``<checkout>/.jax_cache`` (git-ignored).
  The path is fixed, never built from a temp name, a pid or a time, so
  that a later process on the same checkout finds the same entries.

Either way the cache key holds the programs' metadata (op names with
their named scopes, source lines). JAX leaves it out by default, and an
executable that another version of the code compiled, with the same ops,
would then bring that version's op names into this process's profiles.
"""
from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
