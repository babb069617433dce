"""Chunked, sharded, jit-cache-friendly batched config evaluation.

The what-if engine answers the paper's questions by evaluating the analytic
job model (:func:`repro.core.hadoop.model.job_model_jnp`) over *grids* of
configurations.  The seed implementation materialized the whole grid in one
``jit(vmap(...))`` call — one compile per grid size, everything on one
device.  :class:`ChunkedEvaluator` replaces it with a streaming design:

* **Fixed-size padded chunks** — every batch is padded (edge-replicated) to
  one static ``chunk`` length, so XLA compiles exactly once per swept
  key-set no matter how the grid size varies (bounded device memory, no
  recompiles).
* **Device sharding** — each chunk is split across all available devices
  with ``shard_map`` over a 1-D ``search`` mesh (via :mod:`repro.compat`).
  Rows are independent, so the chunked/sharded results agree with the
  unchunked single-device path within :data:`CROSS_PROGRAM_MAX_ULP`, with
  equal ``valid`` flags and top-k order (asserted by tests and
  ``benchmarks/bench_whatif``); one executable is bit-for-bit repeatable.
* **On-device top-k** — ``chunk_topk`` reduces each chunk to its ``k`` best
  (and ``k`` best *invalid*) candidates on device, so a 10^6-config search
  transfers k values per chunk to the host instead of the whole grid.
* **Invalid-config escape hatch** — configs with ``valid == 0`` (closed-form
  merge math out of domain, paper §2.3) are *not* silently ``inf``: top-k
  survivors are routed to :meth:`exact_cost`, the task-scheduler simulator
  (:mod:`repro.core.hadoop.simulator`) whose per-task costs use the exact
  merge simulation.

The same interface is implemented by :class:`repro.search.tpu.TpuEvaluator`
for the TPU-side tuner, so every strategy in
:mod:`repro.search.strategies` runs against either cost model.
"""

from __future__ import annotations

import functools
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Mapping

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import compat
from repro.obs import current as _obs_current
from repro.core.hadoop.model import job_model_jnp, pack_config
from repro.core.hadoop.params import CostFactors, HadoopParams, ProfileStats
from repro.core.hadoop.simulator import SimConfig, simulate_job
from repro.spec import CostReport, JobSpec, ParamSpace, hadoop_space
from repro.spec.report import VALIDITY_CONSTRAINTS

__all__ = [
    "InvalidGridError",
    "ExactCostUnavailable",
    "NotDifferentiableError",
    "SearchResult",
    "BlockTopK",
    "Evaluator",
    "ChunkedEvaluator",
    "cached_evaluator",
    "evaluate_unchunked",
    "apply_assignment",
    "split_overrides",
    "pad_block",
    "sanitize_costs",
    "masked_total",
    "ulp_distance",
    "CROSS_PROGRAM_MAX_ULP",
]


class InvalidGridError(ValueError):
    """Every configuration in the evaluated grid was invalid (no finite cost)."""


class NotDifferentiableError(TypeError):
    """This backend's cost is not a differentiable function of its knobs.

    Raised by :meth:`Evaluator.grad_objective` on backends whose cost comes
    from a simulation or table lookup (the cluster DES, the numpy TPU step
    model).  Gradient-based strategies catch it and fall back — loudly — to
    a zeroth-order strategy.
    """


class ExactCostUnavailable(ValueError):
    """``exact_cost`` cannot produce a finite cost for this one candidate
    (e.g. the cluster DES reports the workload never finishes there).

    Raised instead of returning a silent ``inf``: direct callers get the
    explicit failure, while the generic fallback paths (streamed top-k,
    coordinate descent, the what-if service) catch it, log, and leave that
    candidate at ``inf`` rather than aborting a whole completed search.
    """


@dataclass
class SearchResult:
    """Batched model outputs plus the override grid that produced them."""

    overrides: dict[str, np.ndarray]    # key -> (B,) values
    outputs: dict[str, np.ndarray]      # model key -> (B,) values
    total_cost: np.ndarray              # (B,) seconds (inf where invalid)

    def best(self) -> tuple[int, float, dict[str, float]]:
        """Index, cost and override assignment of the cheapest valid config.

        Raises :class:`InvalidGridError` if no config is valid — the seed
        version silently returned index 0 (an invalid config) in that case.
        """
        if self.total_cost.size == 0 or not np.isfinite(self.total_cost).any():
            raise InvalidGridError(
                "no valid configuration in the grid (all costs are inf); "
                "use repro.search.search_topk(exact_fallback=True) to route "
                "invalid configs through the exact simulator instead"
            )
        i = int(np.argmin(self.total_cost))
        return i, float(self.total_cost[i]), {
            k: float(v[i]) for k, v in self.overrides.items()
        }


def apply_assignment(
    p: HadoopParams,
    s: ProfileStats,
    c: CostFactors,
    assignment: Mapping[str, float],
) -> tuple[HadoopParams, ProfileStats, CostFactors]:
    """Route a flat {config key: value} assignment onto the three parameter
    dataclasses with proper int/bool coercion.

    Thin adapter over :meth:`repro.spec.ParamSpace.apply` — the axis kinds
    of :func:`repro.spec.hadoop_space` are the single source of coercion.
    """
    return hadoop_space().apply(assignment, p, s, c)


def sanitize_costs(raw, xp=np):
    """NaN/±inf -> +inf, so one bad row can never win a min/top-k.

    The ONE implementation of the cost_key sanitization rule, shared by
    every evaluator's host (numpy) and device (``xp=jnp``) reductions.
    """
    return xp.nan_to_num(raw, nan=xp.inf, posinf=xp.inf, neginf=xp.inf)


#: Largest :func:`ulp_distance` allowed between two different executables of
#: the same model on the same rows: sharded vs single-device, chunked vs
#: unchunked.  XLA promises no bitwise equality across programs (fusion and
#: FMA contraction differ with the shapes it compiles for); 1 ulp has been
#: observed on the CPU.  Runs of one executable stay bit-for-bit.
CROSS_PROGRAM_MAX_ULP = 4


def ulp_distance(a, b) -> np.ndarray:
    """Elementwise distance between two float arrays in units in the last
    place of ``a``'s dtype: 0 when bit-equal (``+0 == -0``, ``inf == inf``),
    1 for adjacent floats.  Values of opposite sign count as infinitely far
    apart (``int64`` max)."""
    a = np.asarray(a)
    if a.dtype.kind != "f":
        raise TypeError(f"ulp_distance needs float arrays, got {a.dtype}")
    b = np.asarray(b, dtype=a.dtype)
    ity = np.dtype(f"i{a.dtype.itemsize}")
    lo = np.iinfo(ity).min

    def key(x):     # bit pattern -> integer that is monotonic in the float
        i = x.view(ity).astype(np.int64)
        return np.where(i < 0, lo - i, i)

    ka, kb = key(a), key(b)
    return np.where((ka < 0) == (kb < 0), np.abs(ka - kb),
                    np.iinfo(np.int64).max)


def masked_total(outputs: Mapping[str, Any], cost_key: str, xp=np):
    """The canonical total-cost column: model cost where ``valid``, else inf.

    Shared by :class:`ChunkedEvaluator`, the cluster planner and the what-if
    service so the invalid-row convention cannot drift between backends.

    Gradient safety: this ``where`` zeroes the cotangent of masked rows, but
    a zero cotangent times an infinite *local* derivative upstream is still
    NaN (the classic where/inf bug).  The fix lives at the producers — the
    dangerous divisions in ``core/hadoop/model.py`` are double-``where``
    guarded and round counts use the straight-through helpers — so
    ``jax.grad`` of this masked total is finite even on invalid configs
    (regression-tested in ``tests/test_gradients.py``).  ``sanitize_costs``
    and ``topk.py`` run host-side on already-materialized numpy values and
    carry no gradients, so they need no such guard.
    """
    return xp.where(outputs["valid"] > 0, outputs[cost_key], xp.inf)


@dataclass
class BlockTopK:
    """Per-block top-k reduction: k cheapest valid rows, k cheapest invalid
    rows (candidates for the exact escape hatch), and the block valid count.
    Indices are block-local.  ``reason_counts`` says *why* rows were invalid
    (per closed-form constraint of :data:`repro.spec.VALIDITY_CONSTRAINTS`),
    for backends whose outputs expose the disaggregated flags."""

    costs: np.ndarray
    idx: np.ndarray
    inv_costs: np.ndarray
    inv_idx: np.ndarray
    n_valid: int
    reason_counts: dict[str, int] = field(default_factory=dict)


class Evaluator:
    """Interface every search backend implements.

    ``evaluate`` returns full per-config outputs; ``chunk_topk`` reduces one
    block to its best candidates; ``exact_cost`` (optional) is the escape
    hatch for ``valid == 0`` survivors.  The base class provides a numpy
    ``chunk_topk`` on top of ``evaluate``; accelerator-backed evaluators
    override it with an on-device reduction.
    """

    chunk: int = 4096

    def evaluate(self, overrides: Mapping[str, Any]) -> SearchResult:
        raise NotImplementedError

    def evaluate_small(self, overrides: Mapping[str, Any]) -> SearchResult:
        """Hook for tiny ad-hoc batches; backends with padded fixed-size
        batches override this with an unpadded path."""
        return self.evaluate(overrides)

    def exact_cost(self, assignment: Mapping[str, float]) -> float | None:
        """Exact re-cost of one assignment, ``None`` when the backend has no
        exact path.  May raise :class:`ExactCostUnavailable` for a candidate
        whose exact cost is undefined (callers in this package catch it)."""
        return None

    def report(self, overrides: Mapping[str, Any]) -> CostReport | None:
        """Typed per-phase :class:`repro.spec.CostReport` for these rows, or
        ``None`` for backends without a phase decomposition."""
        return None

    @property
    def param_space(self) -> ParamSpace | None:
        """Declarative description of this backend's searchable axes
        (:class:`repro.spec.ParamSpace`), or ``None`` if undeclared."""
        return None

    def grad_objective(self):
        """Differentiable single-config objective, for gradient strategies.

        Returns ``fn({key: jnp scalar}) -> (cost, valid)`` where ``cost`` is
        the *raw* (unmasked) model cost — differentiable w.r.t. every float
        override — and ``valid`` the model's validity flag (0/1, no useful
        gradient).  Backends whose cost is not a differentiable function of
        the knobs raise :class:`NotDifferentiableError` instead; callers
        must catch it and fall back loudly.
        """
        raise NotDifferentiableError(
            f"{type(self).__name__} does not expose a differentiable "
            "objective; use a zeroth-order strategy (grid/random/descent)"
        )

    def chunk_topk(self, overrides: Mapping[str, np.ndarray], k: int) -> "BlockTopK":
        """Top-k of one block: the k cheapest valid configs and the k
        cheapest invalid configs (ranked by raw model cost)."""
        res = self.evaluate(overrides)
        valid = res.outputs["valid"] > 0
        raw = sanitize_costs(res.outputs[self.cost_key])
        cost = np.where(valid, raw, np.inf)
        inv = np.where(~valid, raw, np.inf)
        kk = min(k, cost.size)
        idx = np.argsort(cost, kind="stable")[:kk]
        inv_idx = np.argsort(inv, kind="stable")[:kk]
        from repro.spec.report import invalid_reason_counts

        # merged cfg gates reduce-side constraints off for map-only rows,
        # matching ChunkedEvaluator._topk_body's on-device counts
        cfg = {**getattr(self, "base_cfg", {}), **overrides}
        return BlockTopK(cost[idx], idx, inv[inv_idx], inv_idx, int(valid.sum()),
                         invalid_reason_counts(res.outputs, cfg or None))

    @property
    def cost_key(self) -> str:
        return "j_totalCost"


def split_overrides(
    base_cfg: Mapping[str, Any], overrides: Mapping[str, Any]
) -> tuple[dict[str, np.ndarray], dict[str, Any], int]:
    """Validate + cast an override mapping against ``base_cfg``: 1-D values
    become batched ``(n,)`` columns sharing one length, scalars are merged
    onto the base as statics.  Each override takes ``base_cfg``'s dtype for
    its key, so service-normalized rows and direct calls see bit-identical
    inputs.  One implementation shared by every chunked evaluator (Hadoop
    job model here, cluster planner in :mod:`repro.cluster.evaluator`) so
    the contract cannot drift."""
    static = dict(base_cfg)
    batched: dict[str, np.ndarray] = {}
    n = None
    for k, v in overrides.items():
        if k not in base_cfg:
            raise KeyError(f"unknown config key: {k!r}")
        arr = jnp.asarray(v, dtype=base_cfg[k].dtype)
        if arr.ndim > 1:
            raise ValueError(f"override {k!r} must be scalar or 1-D")
        if arr.ndim == 1:
            if n is None:
                n = arr.shape[0]
            elif arr.shape[0] != n:
                raise ValueError("all batched overrides must share a length")
            batched[k] = np.asarray(arr)
        else:
            static[k] = arr
    if n is None:
        raise ValueError("at least one override must be batched")
    if n == 0:
        raise ValueError("batched overrides are empty (0-length grid)")
    return batched, static, n


def pad_block(
    batched: Mapping[str, np.ndarray], start: int, stop: int, chunk: int
) -> tuple[dict[str, np.ndarray], np.ndarray]:
    """One ``(chunk,)``-padded slice ``[start, stop)``: edge-replicated
    values + liveness mask.  Static shape => one compile per key-set for
    any grid size."""
    n = stop - start
    pad = chunk - n
    cols = {}
    for k, v in batched.items():
        sl = v[start:stop]
        cols[k] = np.concatenate([sl, np.full(pad, sl[-1], dtype=sl.dtype)]) \
            if pad else sl
    mask = np.zeros(chunk, dtype=bool)
    mask[:n] = True
    return cols, mask


def evaluate_unchunked(
    base_cfg: dict,
    overrides: Mapping[str, jnp.ndarray],
    model_fn: Callable[[dict], dict] = job_model_jnp,
) -> dict:
    """Single-device single-call ``jit(vmap(model))`` — the seed path.

    Kept as the reference the chunked/sharded path is verified against,
    within :data:`CROSS_PROGRAM_MAX_ULP` (tests + ``bench_whatif``).
    Compiles once per batch *size*.
    """
    cfg = dict(base_cfg)
    cfg.update({k: jnp.asarray(v) for k, v in overrides.items()})
    out = _unchunked_jit(model_fn)(cfg)
    return {k: np.asarray(v) for k, v in out.items()}


@functools.lru_cache(maxsize=None)
def _unchunked_jit(model_fn):
    @jax.jit
    def run(cfg: dict) -> dict:
        batched = {k: v for k, v in cfg.items() if jnp.ndim(v) > 0}
        static = {k: v for k, v in cfg.items() if jnp.ndim(v) == 0}
        return jax.vmap(lambda b: model_fn({**static, **b}))(batched)

    return run


class ChunkedEvaluator(Evaluator):
    """Streaming sharded evaluator over the Hadoop job model.

    Parameters
    ----------
    p, s, c : the base configuration (any field may be overridden per-row).
    chunk   : static rows per evaluation call (rounded up to a multiple of
              the device count).  One XLA compile per swept key-set.
    devices : devices to shard chunks over (default: all local devices).
    model_fn: batched model, flat cfg dict -> flat outputs dict; must emit
              ``j_totalCost`` and ``valid``.
    """

    def __init__(
        self,
        p: HadoopParams,
        s: ProfileStats,
        c: CostFactors,
        *,
        chunk: int = 1 << 13,
        devices=None,
        model_fn: Callable[[dict], dict] = job_model_jnp,
    ):
        self._psc = (p, s, c)
        #: typed view of the base configuration (repro.spec.JobSpec)
        self.spec = JobSpec(p, s, c)
        #: packed base config (flat key -> jnp scalar); public so callers can
        #: drive evaluate_unchunked against the exact same base
        self.base_cfg = pack_config(p, s, c)
        self._model_fn = model_fn
        devs = list(devices) if devices is not None else compat.default_search_devices()
        self.num_devices = len(devs)
        self.chunk = -(-max(chunk, 1) // self.num_devices) * self.num_devices
        self._mesh = compat.make_mesh(devs, axis="search")

        body = self._sharded_body()
        self._eval_fn = jax.jit(body)
        self._topk_fn = jax.jit(
            functools.partial(self._topk_body, body), static_argnames=("k",)
        )

    @classmethod
    def from_spec(cls, spec: JobSpec, **kw) -> "ChunkedEvaluator":
        """Construct from a typed :class:`repro.spec.JobSpec` — the typed
        spelling of ``ChunkedEvaluator(p, s, c)``, bit-for-bit identical."""
        return cls(spec.params, spec.stats, spec.costs, **kw)

    @property
    def param_space(self) -> ParamSpace:
        """The paper's Tables-1-3 axes (:func:`repro.spec.hadoop_space`)."""
        return hadoop_space()

    # ---------------- compiled bodies ----------------

    def _sharded_body(self):
        model_fn = self._model_fn
        mesh = self._mesh

        def per_device(batched, static):
            return jax.vmap(lambda b: model_fn({**static, **b}))(batched)

        return compat.shard_map(
            per_device,
            mesh=mesh,
            in_specs=(P("search"), P()),
            out_specs=P("search"),
            check_vma=False,
        )

    def _topk_body(self, body, batched, static, mask, *, k):
        out = body(batched, static)
        raw = sanitize_costs(out[self.cost_key], xp=jnp)
        live = mask > 0
        valid = (out["valid"] > 0) & live
        cost = jnp.where(valid, raw, jnp.inf)
        inv = jnp.where(~(out["valid"] > 0) & live, raw, jnp.inf)
        neg_c, idx = jax.lax.top_k(-cost, k)
        neg_i, inv_idx = jax.lax.top_k(-inv, k)
        # per-constraint invalidity counts ride the same device reduction,
        # so the escape-hatch log can say WHICH closed-form domain failed.
        # Reduce-side flags are zeroed by the model for map-only rows; gate
        # them on pNumReducers so they do not over-report there.
        has_red = (batched["pNumReducers"] if "pNumReducers" in batched
                   else static["pNumReducers"]) > 0
        reasons = {}
        for name, (key, reduce_side, _) in VALIDITY_CONSTRAINTS.items():
            if key not in out:
                continue
            failed = (out[key] == 0) & live
            if reduce_side:
                failed = failed & has_red
            reasons[name] = jnp.sum(failed)
        return -neg_c, idx, -neg_i, inv_idx, jnp.sum(valid), reasons

    # ---------------- padding / packing ----------------

    def _split(self, overrides: Mapping[str, Any]):
        """Validate + cast overrides; split into batched columns and scalar
        (static) overrides merged onto the base config."""
        return split_overrides(self.base_cfg, overrides)

    def _pad(self, batched: Mapping[str, np.ndarray], start: int, stop: int):
        """One (chunk,)-padded slice (see :func:`pad_block`)."""
        return pad_block(batched, start, stop, self.chunk)

    # ---------------- public API ----------------

    def evaluate(self, overrides: Mapping[str, Any]) -> SearchResult:
        """Full outputs for every row, streamed through fixed-size chunks.

        Agrees with :func:`evaluate_unchunked` on the same overrides within
        :data:`CROSS_PROGRAM_MAX_ULP` (padding rows are computed but dropped
        here).
        """
        batched, static, n = self._split(overrides)
        ob = _obs_current()
        t0 = time.perf_counter() if ob.enabled else 0.0
        out_blocks: dict[str, list[np.ndarray]] = {}
        with ob.tracer.span("evaluator.evaluate", rows=n):
            for start in range(0, n, self.chunk):
                stop = min(start + self.chunk, n)
                cols, _ = self._pad(batched, start, stop)
                pre = self.eval_cache_size() if ob.enabled else 0
                out = self._eval_fn(cols, static)
                if ob.enabled:
                    self._note_chunk(ob, batched, pre, self.eval_cache_size())
                for k, v in out.items():
                    out_blocks.setdefault(k, []).append(
                        np.asarray(v)[: stop - start])
        if ob.enabled:
            self._note_evaluate(ob, n, time.perf_counter() - t0)
        outputs = {k: np.concatenate(v) for k, v in out_blocks.items()}
        total = masked_total(outputs, self.cost_key)
        return SearchResult(overrides=batched, outputs=outputs, total_cost=total)

    # ---------------- observability (host-side only; never inside jit) ----

    def _note_chunk(self, ob, batched, pre_compiles: int,
                    post_compiles: int) -> None:
        """Per-chunk accounting: the one-compile-per-key-set contract as a
        runtime-observable metric."""
        ob.registry.counter("evaluator.chunks").inc()
        if post_compiles > pre_compiles:
            key_set = ",".join(sorted(batched))
            ob.registry.counter("evaluator.compiles").inc()
            ob.tracer.instant("xla compile", scope="p", key_set=key_set)

    def _note_evaluate(self, ob, n: int, elapsed: float) -> None:
        n_chunks = -(-n // self.chunk)
        padded = n_chunks * self.chunk - n
        reg = ob.registry
        reg.counter("evaluator.rows").inc(n)
        reg.counter("evaluator.rows_padded").inc(padded)
        reg.histogram("evaluator.evaluate_s").record(elapsed)
        if elapsed > 0:
            ob.tracer.counter(
                "evaluator",
                configs_per_s=n / elapsed,
                padding_waste=padded / (n + padded) if n + padded else 0.0,
            )

    def report(self, overrides: Mapping[str, Any]) -> CostReport:
        """Typed per-phase report for these rows (the ``repro.api`` path).

        Evaluates through the identical chunked executable and lifts the
        flat outputs into a :class:`repro.spec.CostReport`; ``total_cost``
        and ``valid`` are the dict path's arrays by reference, so the typed
        path is bit-for-bit the dict path.
        """
        res = self.evaluate(overrides)
        cfg = {k: np.asarray(v) for k, v in self.base_cfg.items()}
        for k, v in overrides.items():
            cfg[k] = np.asarray(v, dtype=cfg[k].dtype)
        return CostReport.from_outputs(res.outputs, cfg)

    def evaluate_small(self, overrides: Mapping[str, Any]) -> SearchResult:
        """Tiny ad-hoc batches without padding to the full chunk: rows are
        padded to the next power of two instead, so compiles stay bounded
        (one per bucket) while the evaluated-row waste stays < 2x.  Batches
        at or beyond the chunk size take the normal chunked path.

        Note: for *repeated* small sweeps (coordinate descent) the chunked
        :meth:`evaluate` is usually faster end-to-end — its one executable
        is already compiled, and padded rows are cheaper than a retrace."""
        batched, static, n = self._split(overrides)
        if n >= self.chunk:
            return self.evaluate(overrides)
        bucket = 1 << (n - 1).bit_length() if n > 1 else 1
        padded = {
            k: np.concatenate([v, np.full(bucket - n, v[-1], dtype=v.dtype)])
            for k, v in batched.items()
        }
        out = evaluate_unchunked(static, padded, self._model_fn)
        out = {k: v[:n] for k, v in out.items()}
        total = masked_total(out, self.cost_key)
        return SearchResult(overrides=batched, outputs=out, total_cost=total)

    def chunk_topk(self, overrides: Mapping[str, np.ndarray], k: int) -> BlockTopK:
        """On-device top-k of one block (k cheapest valid / invalid rows);
        only 2k scalars + indices come back to the host."""
        batched, static, n = self._split(overrides)
        if n > self.chunk:
            raise ValueError(f"block of {n} rows exceeds chunk={self.chunk}")
        cols, mask = self._pad(batched, 0, n)
        kk = min(k, self.chunk)
        ob = _obs_current()
        with ob.tracer.span("evaluator.chunk_topk", rows=n, k=kk):
            pre = self.topk_cache_size() if ob.enabled else 0
            costs, idx, inv_c, inv_i, n_valid, reasons = self._topk_fn(
                cols, static, mask, k=kk)
        if ob.enabled:
            reg = ob.registry
            reg.counter("evaluator.topk_blocks").inc()
            reg.counter("evaluator.rows").inc(n)
            reg.counter("evaluator.rows_padded").inc(self.chunk - n)
            if self.topk_cache_size() > pre:
                reg.counter("evaluator.compiles").inc()
                ob.tracer.instant("xla compile", scope="p",
                                  key_set=",".join(sorted(batched)))
        return BlockTopK(
            np.asarray(costs), np.asarray(idx),
            np.asarray(inv_c), np.asarray(inv_i), int(n_valid),
            {name: int(v) for name, v in reasons.items() if int(v)},
        )

    def grad_objective(self):
        """The job model as a differentiable objective: the branch-free
        equations with straight-through round counts, evaluated on one
        config (base + scalar overrides).  Same ``model_fn`` as the chunked
        path, so the value at any point agrees with :meth:`evaluate`."""
        base = self.base_cfg
        model_fn = self._model_fn
        cost_key = self.cost_key

        def objective(overrides: Mapping[str, Any]):
            out = model_fn({**base, **overrides})
            return out[cost_key], out["valid"]

        return objective

    def exact_cost(self, assignment: Mapping[str, float]) -> float:
        """Escape hatch for ``valid == 0``: exact task-scheduler simulation
        (paper §5 way (i)); its per-task merge accounting uses the exact
        merge simulation, so it has no closed-form domain restriction."""
        p2, s2, c2 = apply_assignment(*self._psc, assignment)
        return float(simulate_job(p2, s2, c2, SimConfig()).makespan)

    # compile-cache introspection (used by tests/bench to prove chunking
    # keeps one compile across grid sizes)
    def eval_cache_size(self) -> int:
        return self._eval_fn._cache_size()

    def topk_cache_size(self) -> int:
        return self._topk_fn._cache_size()


# The parameter dataclasses are frozen (hashable), so repeated calls through
# the legacy whatif/tuner APIs with the same base config reuse one evaluator
# — and with it the compiled chunk executables, matching the seed's
# module-level jit cache.
@functools.lru_cache(maxsize=16)
def cached_evaluator(
    p: HadoopParams,
    s: ProfileStats,
    c: CostFactors,
    chunk: int | None = None,
) -> ChunkedEvaluator:
    kw = {} if chunk is None else {"chunk": chunk}
    return ChunkedEvaluator(p, s, c, **kw)
