"""Where the entry points keep JAX's persistent compilation cache.

The cache key includes the cache path, so a directory that moves (a
tmpdir, a pid) never hits.  When ``JAX_COMPILATION_CACHE_DIR`` is set,
JAX reads it itself and nothing here overrides it; otherwise the cache
lives at the fixed, git-ignored ``<repo>/.jax_cache``.

Called by the command-line entry points (``launch/train.py``,
``launch/serve.py``, ``chip_smoke.py``) before their first compile —
never on import, so the test suite runs without a persistent cache.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["use_compile_cache"]

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Place the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
