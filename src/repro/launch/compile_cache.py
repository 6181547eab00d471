"""JAX's persistent compilation cache for the entry points.

A cold chip process spends minutes compiling the train step; the cache
lets the next process on the same disk skip that. A process finds only
what was written to the directory it looks in, so the directory is fixed
rather than made per run: ``JAX_COMPILATION_CACHE_DIR``
when it is set (JAX reads that variable itself), else ``<repo>/.jax_cache``
(git-ignored). Called from ``main()`` of the examples and of
``chip_smoke.py`` — never at import, and never from the tests.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
