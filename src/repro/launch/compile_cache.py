"""Persistent XLA compilation cache, placed from outside or in the checkout.

Entry points (``launch/serve.py``, ``launch/train.py``, ``chip_smoke.py``)
call :func:`enable_compile_cache` before their first compile.  Library
code and the tests never do, so a test run leaves no cache behind.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CHECKOUT_CACHE_DIR", "enable_compile_cache"]

# One fixed path: the cache directory is part of what a later run must
# find again, so it is never derived from a temporary name, pid or time.
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it and
    nothing is overridden.  Otherwise the cache goes to
    :data:`CHECKOUT_CACHE_DIR` (ignored by git)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
