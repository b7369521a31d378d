"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, the examples, the benchmarks and
``launch/train.py``) call :func:`enable_compile_cache` once before they
compile anything.  Library code and tests never do.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# A fixed path inside the checkout: the cache directory is part of what a
# later run must find again, so it is never a temp name, pid or timestamp.
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it and
    nothing is set here.  Otherwise the cache goes to ``DEFAULT_DIR``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
