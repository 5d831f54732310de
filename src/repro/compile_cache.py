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
    no directory is set in code. Otherwise the cache lives at the fixed
    ``<repo root>/.jax_cache`` (git-ignored), so every process of one
    checkout finds what an earlier one compiled. Either way the cache is
    keyed by op names (:func:`key_cache_by_op_names`).
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(REPO_ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    key_cache_by_op_names()
    return path


def key_cache_by_op_names() -> None:
    """Make op names, and not source locations, part of the cache key.

    A directory from ``JAX_COMPILATION_CACHE_DIR`` may be one machine-wide
    cache that checkouts of different code share. JAX's default key leaves
    op metadata out, so it would hand this code an executable compiled from
    code without the ``paris.*`` named scopes, and a profile of it would
    find none. With metadata in the key, source locations would make the
    key depend on the checkout's path, so they are dropped from the
    metadata: the cost is that HLO metadata, and so a profile's ``source``
    stat and XLA's error messages, carry no Python file and line.
    """
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_traceback_in_locations_limit", 0)
