"""Where JAX's persistent compilation cache lives for this checkout.

The cache's path is part of every entry's key, so it must not move
between runs: no tempfile, pid or clock in it. The placement comes from
outside when `JAX_COMPILATION_CACHE_DIR` is set (JAX reads that variable
itself, and nothing is set in code); otherwise it is the checkout's own
`.jax_cache/` (git-ignored). Entry points (`chip_smoke.py`, `bench.py`)
call `place_compile_cache()` once, before their first compile.
"""
from __future__ import annotations

import os

__all__ = ["place_compile_cache", "CHECKOUT_CACHE_DIR"]

CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def place_compile_cache() -> str:
    """Returns the cache directory in effect. Sets it in code only where
    the environment does not."""
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    import jax
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
