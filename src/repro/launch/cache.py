"""JAX's persistent compilation cache, for the entry points.

Called from ``chip_smoke.py`` and ``benchmarks/run.py``'s ``main`` — never
on import of ``repro``.  ``JAX_COMPILATION_CACHE_DIR``, when set, is the
cache (JAX reads it itself); otherwise the cache lives at a fixed path in
the checkout, ``<repo>/.jax_cache`` — fixed, because the path is part of
what a later process must find.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
