"""Placement of JAX's persistent compilation cache.

One rule for every entry point that compiles on the chip
(``chip_smoke.py``, ``bench.py``, ``benchmarks/cb/main.py``,
``benchmarks/roofline_resnet.py``): where ``JAX_COMPILATION_CACHE_DIR``
is set, JAX reads it itself and the program sets no cache path; where it
is not, the cache is ``<checkout>/.jax_cache`` (git-ignored).  The path
is part of the cache key, so it is never derived from a temp name, a
pid, the time or another knob — a directory that moves never hits.
"""

from __future__ import annotations

import os

import jax

__all__ = ["enable"]

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def enable() -> str:
    """Turn the persistent compilation cache on and return its directory."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
    if not placed:
        placed = os.path.join(_CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", placed)
    # a run compiles hundreds of sub-second programs beside the few long
    # ones; cache them all
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return placed
