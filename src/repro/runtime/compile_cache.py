"""JAX's persistent compilation cache, placed from outside or at a fixed path.

Entry points (``chip_smoke.py``, ``examples/*.py``, ``launch/train.py``,
``benchmarks/run.py``) call :func:`enable_compile_cache` once, before their
first compile.  Importing the library never does: a test or an embedding
application keeps whatever cache policy it has.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

# <checkout>/.jax_cache: a fixed path (the cache key includes nothing that
# moves), listed in .gitignore
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    A set ``JAX_COMPILATION_CACHE_DIR`` wins and no other directory is set;
    otherwise the cache lives at :data:`DEFAULT_DIR`, so later runs from the
    same checkout find what earlier ones compiled."""
    path = os.environ.get(ENV_VAR) or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
