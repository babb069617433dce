"""Where JAX keeps its persistent compilation cache for this repo's entry points.

``chip_smoke.py`` and the benchmark entry points (``benchmarks/run.py``,
``benchmarks.common.bench_main``) call :func:`use_compile_cache` once at
start-up, before their first compile:

* where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
  nothing is set in code;
* otherwise the cache goes to ``.jax_cache/`` at the root of the checkout, a
  fixed path (JAX keys cache entries by it, so a path that moved between
  runs would never hit).

The test suite does not call it, so tests never turn the cache on.
"""

from __future__ import annotations

import os
from pathlib import Path
from typing import Mapping

__all__ = ["CACHE_ENV", "CACHE_DIRNAME", "compile_cache_dir", "use_compile_cache"]

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
CACHE_DIRNAME = ".jax_cache"


def compile_cache_dir(root: str | os.PathLike,
                      environ: Mapping[str, str] = os.environ
                      ) -> tuple[str, bool]:
    """``(cache directory, whether code must set it)`` for a checkout at
    ``root``: the environment's directory when it names one, else
    ``<root>/.jax_cache``."""
    if environ.get(CACHE_ENV):
        return environ[CACHE_ENV], False
    return str(Path(root).resolve() / CACHE_DIRNAME), True


def use_compile_cache(root: str | os.PathLike) -> str:
    """Turn JAX's persistent compilation cache on for the checkout at
    ``root`` (see the module docstring); returns the cache directory."""
    path, set_in_code = compile_cache_dir(root)
    if set_in_code:
        import jax

        jax.config.update("jax_compilation_cache_dir", path)
    return path
