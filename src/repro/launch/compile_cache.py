"""Where JAX's persistent compilation cache lives.

The cache key includes its directory, so the directory must not move between
runs: it is ``$JAX_COMPILATION_CACHE_DIR`` when that is set (JAX reads the
variable itself), and otherwise ``.jax_cache`` at the root of the checkout.
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on before the first compile; returns its path."""
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
