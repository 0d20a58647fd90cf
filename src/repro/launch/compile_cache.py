"""JAX's persistent compilation cache for the repo's entry points.

Called by a script's ``main`` before its first compile — never at import
time and never from tests: an AOT compile for a described (unattached)
chip writes entries that no later run can read back.
"""
from __future__ import annotations

import os
import pathlib

import jax

CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own choice and wins;
    otherwise the cache lives at the fixed ``.jax_cache/`` of this checkout,
    where the next run from the same checkout finds it."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
