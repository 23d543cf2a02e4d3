"""JAX's persistent compilation cache for the entry points.

Entry points (``chip_smoke.py``, ``launch/serve.py``, ``launch/train.py``,
``benchmarks/run.py``) call ``enable_compile_cache()`` once at start-up;
library imports and tests never do.  Where ``JAX_COMPILATION_CACHE_DIR`` is
set, JAX reads it itself and nothing here overrides it.  Otherwise the cache
goes to one fixed directory inside the checkout: the cache key includes the
path, so a directory named after a process, a temporary name or the time
would never hit.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/.jax_cache (this file is <checkout>/src/repro/launch/...).
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
