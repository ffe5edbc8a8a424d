"""JAX's persistent compilation cache, placed from outside the program.

Entry points (``main()`` of the servers and benchmark scripts, and
``chip_smoke.py``) call :func:`enable_compile_cache` once before their first
compile; nothing calls it at import time, so library users and tests keep
JAX's defaults.

Where the cache lives: ``JAX_COMPILATION_CACHE_DIR`` when it is set (JAX
reads that variable itself and this module sets no other directory),
otherwise the fixed ``.jax_cache/`` at the repository root (gitignored).
The directory is part of what makes an entry findable again, so it never
moves between runs of the same checkout.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; → its directory.

    Every compile is cached (no minimum compile time): the Pallas kernels
    compile in well under JAX's default one-second threshold, and a cold
    process on the chip pays for each of them."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
