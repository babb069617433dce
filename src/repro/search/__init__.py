"""repro.search — the config-search subsystem.

The paper exists to answer what-if questions and find optimal
configurations.  This package is the platform for that at production scale:

* :mod:`~repro.search.grid`       — streaming Cartesian spaces (no
  materialized 10^6-row products).
* :mod:`~repro.search.evaluator`  — chunked, padded, device-sharded batched
  model evaluation (one XLA compile per key-set; within
  ``CROSS_PROGRAM_MAX_ULP`` of the unchunked path) + the ``valid == 0`` ->
  exact-simulator escape hatch.
* :mod:`~repro.search.topk`       — streaming on-device top-k merging.
* :mod:`~repro.search.strategies` — grid / random / coordinate-descent /
  gradient-descent search over any evaluator (gradient descent relaxes the
  space continuously and differentiates the model itself, falling back
  loudly on non-differentiable backends).
* :mod:`~repro.search.service`    — async what-if query service: concurrent
  probes/sweeps/grids coalesced into shared evaluator chunks (continuous
  batching over row slots, per-query futures + latency stats).
* :mod:`~repro.search.tpu`        — the TPU step model behind the same
  evaluator interface.

jax API spellings (``shard_map`` et al.) come from :mod:`repro.compat`.
The seed modules ``repro.core.whatif`` and ``repro.core.tuner`` remain as
thin aliases of this package.
"""

from .evaluator import (
    CROSS_PROGRAM_MAX_ULP,
    BlockTopK,
    ChunkedEvaluator,
    Evaluator,
    ExactCostUnavailable,
    InvalidGridError,
    NotDifferentiableError,
    SearchResult,
    apply_assignment,
    cached_evaluator,
    evaluate_unchunked,
    masked_total,
    sanitize_costs,
    ulp_distance,
)
from .grid import assignment_at, iter_blocks, sample_space, space_block, space_size
from .service import PhaseQueryResult, QueryResult, QueryStats, WhatIfService
from .strategies import (
    TuningResult,
    coordinate_descent,
    coordinate_descent_ev,
    gradient_descent_ev,
    grid_search,
    grid_search_ev,
    random_search,
    random_search_ev,
    search_topk,
)
from .topk import TopKAccumulator, TopKEntry, TopKResult
from .tpu import TpuEvaluator, mesh_space, tune_tpu

__all__ = [
    "ExactCostUnavailable",
    "InvalidGridError",
    "NotDifferentiableError",
    "SearchResult",
    "BlockTopK",
    "Evaluator",
    "ChunkedEvaluator",
    "cached_evaluator",
    "evaluate_unchunked",
    "apply_assignment",
    "sanitize_costs",
    "masked_total",
    "ulp_distance",
    "CROSS_PROGRAM_MAX_ULP",
    "space_size",
    "space_block",
    "iter_blocks",
    "sample_space",
    "assignment_at",
    "TopKEntry",
    "TopKResult",
    "TopKAccumulator",
    "TuningResult",
    "search_topk",
    "grid_search",
    "grid_search_ev",
    "random_search",
    "random_search_ev",
    "coordinate_descent",
    "coordinate_descent_ev",
    "gradient_descent_ev",
    "WhatIfService",
    "QueryResult",
    "QueryStats",
    "PhaseQueryResult",
    "TpuEvaluator",
    "mesh_space",
    "tune_tpu",
]
