"""Placement of JAX's persistent compilation cache — one rule for the
tests, the benchmark, chip_smoke.py and the scripts."""

from __future__ import annotations

import os

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def use_compile_cache() -> str:
    """Where `JAX_COMPILATION_CACHE_DIR` is set JAX already honors it and
    nothing is set here; otherwise the cache goes to
    `<checkout>/.jax_cache` (a fixed path: the directory is part of the
    cache key).  Returns the directory in effect."""
    import jax

    if not os.environ.get(CACHE_ENV):
        jax.config.update(
            "jax_compilation_cache_dir", os.path.join(_CHECKOUT, ".jax_cache")
        )
    return jax.config.jax_compilation_cache_dir
