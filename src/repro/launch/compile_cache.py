"""JAX's persistent compilation cache, placed for the entry points.

A cold compile of a verifier step at published widths takes tens of
seconds, so every entry point (``chip_smoke.py``, ``repro.launch.serve``
and the ``benchmarks/*`` mains) calls :func:`use_compile_cache` before
its first compile.  Nothing here runs at import time.
"""
from __future__ import annotations

import os
from pathlib import Path

# A fixed directory inside the checkout (listed in .gitignore).  JAX
# finds a cached program again only under the same directory, so the
# path never depends on a temporary name, a process id or the time.
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it by itself and
    nothing is changed here; otherwise the cache goes to ``CACHE_DIR``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
