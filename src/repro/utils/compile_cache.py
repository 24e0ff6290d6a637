"""JAX's persistent compilation cache for the repository's entry points.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``) call
``use_compile_cache()`` once before they compile anything; library code
never does. The cache's path is part of its key, so it is fixed.
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """Point JAX's compilation cache at ``<checkout>/.jax_cache``, unless
    ``JAX_COMPILATION_CACHE_DIR`` is set: JAX then uses that directory and
    nothing is set here. Returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
