"""JAX's persistent compilation cache for the entry points.

Each entry point (``chip_smoke.py``, ``launch/serve.py``,
``launch/train.py``) calls ``use_compile_cache`` before its first compile;
importing this module changes nothing.  The cache key includes the
directory, so it is a fixed path and never derived from a temporary name,
a pid or the time.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

# <checkout>/.jax_cache (listed in .gitignore)
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.  Where
    ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and nothing is
    set here; otherwise the cache lives in the checkout's ``.jax_cache/``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
