"""JAX's persistent compilation cache for the entry points that compile.

``chip_smoke.py`` and ``benchmarks/run.py`` call ``enable_compile_cache``
before their first compile; tests never do.  The cache key includes the
directory, so the directory is fixed: ``JAX_COMPILATION_CACHE_DIR`` when
it is set (JAX reads that variable itself), otherwise ``.jax_cache`` at
the root of the checkout (listed in ``.gitignore``).
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
