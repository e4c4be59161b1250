"""JAX's persistent compile cache at one fixed path inside the checkout.

A cache directory is part of each entry's key, so it must not move from
run to run: ``<checkout>/.jax_cache``, never a temp name, a pid or a
time. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself
and nothing here overrides it.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CACHE_DIR", "use_compile_cache"]

#: ``<checkout>/.jax_cache`` (this file is ``<checkout>/src/repro/launch``)
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compile cache on; returns its directory.

    Call before the first compilation. ``JAX_COMPILATION_CACHE_DIR``,
    when set, stays the only setting; otherwise the cache goes to
    :data:`CACHE_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
