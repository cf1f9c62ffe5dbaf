"""Where an entry point keeps JAX's persistent compilation cache.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``) call
:func:`configure` once at start; importing the library never touches the
cache. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
nothing is set here. Otherwise the cache goes to ``<checkout>/.jax_cache``:
a fixed path, so a later run of the same checkout finds what an earlier
one compiled (``.gitignore`` lists it).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[2]


def configure() -> str:
    """Make sure the compilation cache has a directory; return it."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
