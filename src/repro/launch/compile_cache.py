"""Persistent XLA compilation cache for the program's entry points.

JAX keeps compiled programs across processes in the directory named by
``JAX_COMPILATION_CACHE_DIR``; JAX reads that variable itself, so when it
is set this module sets no directory.  Otherwise the cache goes to
``.jax_cache/`` at the root of the checkout: a fixed path, so that the
next run from the same checkout finds what this one compiled.

JAX writes only programs whose compile took at least
``jax_persistent_cache_min_compile_time_secs`` (1 s by default).  The
discovery kernels are many small bucketed programs that each compile
faster than that, so the threshold is set to 0 and every program is kept.

Only entry points (``python -m repro.launch.serve``, ``chip_smoke.py``,
``python -m benchmarks.run``) call :func:`enable_compile_cache`; library
code and the tests never do.
"""

from __future__ import annotations

import os
from pathlib import Path

__all__ = ["enable_compile_cache", "DEFAULT_CACHE_DIR"]

#: ``<checkout>/.jax_cache`` (this file is ``<checkout>/src/repro/launch/``)
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
