"""Where JAX's persistent compilation cache lives.

Entry points call :func:`use_compile_cache` once at start-up, before
their first compile; importing this module changes nothing.  A cache
directory named in ``JAX_COMPILATION_CACHE_DIR`` is JAX's own setting
and wins.  Otherwise the cache sits at ``<checkout>/.jax_cache`` (git
ignores it): a fixed path, so every process run from one checkout finds
what an earlier one compiled.  The cache key is the program, the jaxlib
and libtpu versions, the XLA flags and the device topology; source
locations are stripped from it, so the checkout's path is not part of
it.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    named = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if named:
        return named
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
