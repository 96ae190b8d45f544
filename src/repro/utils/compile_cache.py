"""JAX's persistent compilation cache, kept at one fixed place.

The cache key includes the directory, so a path that moves between runs
(temporary, per-pid, per-time) never hits.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it at import and this
module sets no other directory; otherwise the cache lives in
``.jax_cache/`` at the checkout root (listed in ``.gitignore``).
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: the checkout root: src/repro/utils/compile_cache.py -> parents[3]
_DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on and return its directory.
    Call before the first compile of the process."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(_DEFAULT_DIR))
    return str(_DEFAULT_DIR)
