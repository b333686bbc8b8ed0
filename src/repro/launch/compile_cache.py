"""JAX's persistent compilation cache, switched on by entry points.

Called from ``chip_smoke.py`` and ``benchmarks/run.py``; importing the
library never touches it.
"""
from __future__ import annotations

import os

import jax


def enable_compile_cache(root: str) -> str:
    """Turn the persistent compilation cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is changed.  Otherwise the cache sits at ``<root>/.jax_cache``
    — a fixed path, so later runs from the same checkout find what earlier
    ones compiled.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(os.path.abspath(root), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
