"""JAX's persistent compilation cache, kept at one fixed place.

Every fused kernel and every (tier, H) window program compiles on its
first call; on a TPU that is seconds per program.  The persistent cache
lets the next process load them instead.  Its entries are keyed on the
cache directory too, so the directory must not move between runs: it is
``JAX_COMPILATION_CACHE_DIR`` when that is set, and otherwise the fixed
``.jax_cache`` at the root of the checkout (git-ignored).
"""
from __future__ import annotations

import os
import pathlib

import jax

#: Where the cache lives when ``JAX_COMPILATION_CACHE_DIR`` is unset.
DEFAULT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already uses it and this
    sets nothing.  Otherwise the cache goes to :data:`DEFAULT_CACHE_DIR`.
    Call it once, before the first compile, from an entry point — never
    at import.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
