"""Where a run keeps what it builds: the checkout root and JAX's persistent
compilation cache.

Every entry point (the launchers, ``chip_smoke.py``) makes the same call,
so compiled programs are found again by the next process on the same
machine.  The cache path is part of what JAX keys on, so it is a fixed
path, never a temporary name.
"""

from __future__ import annotations

import os

__all__ = ["CHECKOUT", "enable_compile_cache"]

# the repository root (this file is <checkout>/src/repro/runtime.py)
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory:
    ``$JAX_COMPILATION_CACHE_DIR`` when set (JAX reads it itself, and no
    other directory is set here), else ``<checkout>/.jax_cache``."""
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
