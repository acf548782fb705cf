"""Where the program keeps state between runs: JAX's persistent compilation
cache and the kernel autotune table.

Both sit at fixed paths inside the checkout (``.cache/`` at the repo root,
listed in ``.gitignore``), so a run reads no state from outside it and a
second run from the same checkout finds what the first one cached.  A
persistent compile cache is keyed partly by its own path, so the path must
never carry a temporary name, a pid or a time.

``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it itself and
:func:`enable_compile_cache` sets no other directory.  The entry points
(``chip_smoke.py``, ``repro.launch.serve``, ``repro.launch.apsp_run``,
``benchmarks.run``) call :func:`enable_compile_cache` once at start; nothing
calls it at import time.
"""

from __future__ import annotations

import os
from pathlib import Path

__all__ = ["CACHE_ROOT", "compile_cache_dir", "enable_compile_cache",
           "autotune_cache_file"]

#: ``<checkout>/.cache`` — this file is ``<checkout>/src/repro/caches.py``.
CACHE_ROOT = Path(__file__).resolve().parents[2] / ".cache"

_ENV = "JAX_COMPILATION_CACHE_DIR"


def compile_cache_dir() -> Path:
    """The persistent compile cache directory this process uses."""
    env = os.environ.get(_ENV, "")
    return Path(env) if env else CACHE_ROOT / "jax"


def enable_compile_cache() -> Path:
    """Turn on JAX's persistent compilation cache at :func:`compile_cache_dir`
    and return that directory.  With ``JAX_COMPILATION_CACHE_DIR`` set, JAX
    already uses it and nothing is changed here."""
    path = compile_cache_dir()
    if not os.environ.get(_ENV, ""):
        import jax

        path.mkdir(parents=True, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", str(path))
    return path


def autotune_cache_file() -> Path:
    """Default autotune table (``REPRO_AUTOTUNE_CACHE`` overrides it)."""
    return CACHE_ROOT / "autotune.json"
