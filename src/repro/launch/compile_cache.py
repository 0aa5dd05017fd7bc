"""JAX's persistent compilation cache, switched on by the entry points.

Library import never touches it: only a ``main()`` (and ``chip_smoke.py``)
calls :func:`enable_compile_cache`. Where ``JAX_COMPILATION_CACHE_DIR`` is
set, JAX reads that directory itself and nothing is set here. Otherwise the
cache goes to ``<repo>/.jax_cache``: a fixed path, because the path is part
of every entry's key and a per-run directory would never hit.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["DEFAULT_CACHE_DIR", "enable_compile_cache"]

DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
