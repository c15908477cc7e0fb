"""JAX's persistent compilation cache, placed so a later run can find it.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``) call
:func:`enable_compile_cache` once at start-up; nothing calls it at import.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

# ``src/repro/compile_cache.py`` → the checkout root is two levels above
# the package.  A fixed directory, never a temporary or per-process one:
# only a run that looks in the same place finds what an earlier one wrote.
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already reads it and this
    sets no other directory.  Otherwise the cache goes to
    ``<checkout>/.jax_cache``.
    """
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
