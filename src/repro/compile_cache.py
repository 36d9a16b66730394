"""JAX's persistent compilation cache, configured in one place.

Entry points that run on the chip (``chip_smoke.py``, ``benchmarks/run.py``)
call ``enable_compile_cache()`` before their first compile, so a later run
of the same checkout loads its programs instead of compiling them again.
"""
from __future__ import annotations

import os
from pathlib import Path

# <checkout>/src/repro/compile_cache.py -> <checkout>/.jax_cache
CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here. Otherwise the cache goes to ``.jax_cache/`` at the
    root of the checkout: a fixed path, since the directory is part of
    what a later run must find again."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
