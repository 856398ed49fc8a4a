"""JAX persistent compilation cache for the entry points.

Called from entry points only (``chip_smoke.py``, ``benchmarks/run.py``,
the ``launch.serve`` / ``launch.train`` CLIs) — never on import, so a
library user's own cache setting is left alone.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CACHE_DIR", "enable_compile_cache"]

# Fixed path at the checkout root: the cache key includes the path, so a
# directory that moved between runs would never hit.
CACHE_DIR = str(Path(__file__).resolve().parents[3] / ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it and
    it is left as it is; otherwise the cache lives in ``CACHE_DIR``.  The
    minimum compile time to cache drops to zero, so kernels that compile in
    a second or two are kept.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
