"""Persistent XLA compilation cache at a fixed path.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
leaves it alone.  Otherwise the cache goes to ``<checkout>/.jax_cache``
(gitignored).  The path must not move between runs — no temp name, pid
or time in it — because a later run finds its entries only there.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CHECKOUT", "use_compile_cache"]

# src/repro/launch/compile_cache.py -> the checkout root
CHECKOUT = Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
