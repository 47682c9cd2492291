"""Persistent XLA compilation cache for the entry points that run on a chip.

Called by ``chip_smoke.py`` and ``repro.launch.serve.main`` — never at import
and never from tests. A deployment places the cache with
``JAX_COMPILATION_CACHE_DIR``, which JAX reads itself; otherwise it lives at
the fixed ``<repo>/.jax_cache`` (gitignored). The path is part of the cache
key, so it is never built from a temporary directory, a pid or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; returns it."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
