"""Persistent compilation cache for the entry points (never set at import).

JAX reads `JAX_COMPILATION_CACHE_DIR` itself; when it is unset the cache
goes to the fixed path `<checkout>/.jax_cache`. The path is part of the
cache's key, so it must not move between runs.
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn the cache on; returns the directory in use."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(CHECKOUT / ".jax_cache"))
    return jax.config.jax_compilation_cache_dir
