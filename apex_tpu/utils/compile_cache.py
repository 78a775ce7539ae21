"""Persistent XLA compile cache, placed from outside or at a fixed path.

The cache directory is part of how a run finds yesterday's executables,
so it is never derived from a temporary name, a pid or a clock: either
the environment names it (``JAX_COMPILATION_CACHE_DIR`` — JAX reads that
variable itself, and this module then sets no directory at all), or it is
``<checkout>/.jax_cache``, resolved from this file's own location.

Called at the top of ``__main__`` entry points only (``chip_smoke.py``,
``bench.py``'s inner legs, the ``examples/*`` scripts) — never from
library code or from a ``main(argv)`` that tests call, so the test
suite's compile-count guards keep counting real compiles.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

__all__ = ["CACHE_DIR_ENV", "default_cache_dir",
           "enable_persistent_compile_cache"]

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"


def default_cache_dir() -> Path:
    """``<checkout>/.jax_cache`` (``.gitignore`` lists it)."""
    return Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_persistent_compile_cache() -> Optional[str]:
    """Turn the persistent compile cache on for this process; returns
    the directory this call set, or ``None`` when it set none: the
    environment placed the cache (``JAX_COMPILATION_CACHE_DIR`` set —
    JAX's config is left alone), or the process runs on the CPU
    platform, where toy-size compiles are cheap and XLA:CPU's loader
    logs a machine-feature error for every entry it reads back.
    Must run before the first compile."""
    if os.environ.get(CACHE_DIR_ENV):
        return None
    import jax

    if jax.default_backend() == "cpu":
        return None
    path = str(default_cache_dir())
    jax.config.update("jax_compilation_cache_dir", path)
    # keep every executable, not only those that took over a second to
    # build: a warm run then compiles nothing the cold one compiled
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
