"""Persistent XLA compilation cache for the repo's entry points.

Scripts (``chip_smoke.py``, ``benchmarks/run.py``, ``examples/``) call
:func:`enable_compile_cache` first thing in ``__main__``; no library module
or test calls it, so importing the package never touches the disk.
"""

from __future__ import annotations

import os

import jax

REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compile cache; returns its directory.

    A set ``JAX_COMPILATION_CACHE_DIR`` is JAX's own setting and wins:
    nothing is set in code. Otherwise the cache lives at the fixed
    ``<repo root>/.jax_cache`` (git-ignored), so every process of one
    checkout finds what an earlier one compiled.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(REPO_ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
