"""The jax APIs this repo uses whose spelling has moved between jax releases.

Policy (see README "jax compat policy"): any such symbol is imported from
HERE, never looked up at the call site.  Every module that needs
``shard_map`` (the MapReduce pipeline, cross-pod reduction, the EP MoE layout
and the config-search evaluator) goes through :func:`shard_map` below, so a
jax upgrade is a one-file change.  Only the installed jax (0.9) is supported.

* ``shard_map`` — ``jax.shard_map`` with its ``check_vma=`` kwarg.
* ``pallas_tpu_compiler_params`` — ``pltpu.CompilerParams``.
* ``make_mesh`` / ``default_search_devices`` — 1-D mesh construction for the
  sharded config-search evaluator (:mod:`repro.search`).
"""

from __future__ import annotations

from typing import Sequence

import jax
import numpy as np
from jax.sharding import Mesh

__all__ = [
    "shard_map",
    "make_mesh",
    "default_search_devices",
    "pallas_tpu_compiler_params",
]


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool | None = None):
    """``jax.shard_map``; ``check_vma=None`` keeps jax's default."""
    kw = {} if check_vma is None else {"check_vma": check_vma}
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kw)


def pallas_tpu_compiler_params(**kwargs):
    """``pltpu.CompilerParams`` with the given fields."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(**kwargs)


def default_search_devices() -> list:
    """All addressable devices, for sharding config-search chunks."""
    return list(jax.local_devices())


def make_mesh(devices: Sequence | None = None, axis: str = "search") -> Mesh:
    """1-D mesh over ``devices`` (default: every local device)."""
    devs = list(devices) if devices is not None else default_search_devices()
    return Mesh(np.asarray(devs), (axis,))
