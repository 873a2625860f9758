"""JAX's persistent compilation cache, placed once per process entry point.

Entry points (``python -m repro.api``, the ``benchmarks/*.py`` mains,
``repro.launch.train`` and ``chip_smoke.py``) call
:func:`enable_compile_cache` first thing; library imports never touch it.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
sets no directory in code.  Otherwise the cache lives at a fixed
``<checkout>/.jax_cache``: the path is part of the cache key, so a
temporary, PID- or time-based directory would never hit.
"""

from __future__ import annotations

import os
from pathlib import Path

__all__ = ["CHECKOUT_CACHE_DIR", "enable_compile_cache"]

#: The fixed fallback location, inside the checkout (listed in .gitignore).
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    # Kernels compile in about a second; keep every program, not only the
    # slow ones, so a warm process compiles nothing.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax.config.jax_compilation_cache_dir
