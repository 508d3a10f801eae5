"""JAX's persistent compilation cache for the toolchain's entry points.

Entry points (``chip_smoke.py``, ``bench/run.py``,
``examples/quickstart.py``) call `enable_compile_cache` before their first
compile; importing this module changes nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["enable_compile_cache"]

# A fixed path inside the checkout: the directory is part of the cache's
# key, so a path that moved between runs would never hit.
_DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> Path:
    """Turn the persistent compilation cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing else is set here; otherwise the cache goes to ``.jax_cache`` at
    the root of the checkout.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return Path(env)
    jax.config.update("jax_compilation_cache_dir", str(_DEFAULT_DIR))
    return _DEFAULT_DIR
