"""Persistent XLA compilation cache for scripts that run Palgol jobs.

A Palgol job compiles one program per fused superstep (staged and
partitioned placements) or per program (the fused dense compiler); at
chip scale the larger ones take tens of seconds. Entry points call
:func:`enable` once, before the first compile, so a second run of the
same job loads its executables instead of compiling them again. Tests do
not call it.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: the checkout this module belongs to (``<checkout>/src/repro/...``)
CHECKOUT = Path(__file__).resolve().parents[2]
#: where the cache lives when ``JAX_COMPILATION_CACHE_DIR`` is not set; a
#: fixed path, because the directory is part of what a cache entry matches
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def enable() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it and
    it stands: nothing else is configured. Otherwise the cache goes to
    :data:`DEFAULT_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
