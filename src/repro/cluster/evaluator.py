"""Capacity planning behind the :class:`repro.search.Evaluator` interface.

``ClusterEvaluator`` makes *cluster* knobs — node count, fleet mix, slots
per node, scheduler policy, preemption, reduce slowstart, offered arrival
rate — searchable by every existing strategy (``grid_search_ev``,
``random_search_ev``, ``coordinate_descent_ev``, streaming
``search_topk``) and servable by :class:`repro.search.WhatIfService`,
exactly like the single-job Hadoop model:

* ``evaluate`` expands each override row into (row x workload-seed)
  scenarios, rolls them out with the vectorized wave simulator
  (:mod:`repro.cluster.vector_sim`), and aggregates per-trace tail metrics;
* the cost is ``mean`` or ``p95`` job latency (submit -> finish), or the
  ``makespan`` (first arrival -> last finish, the elapsed time of a batch
  such as a benchmark's throughput test), averaged over the workload
  seeds — the capacity-planning objective;
* ``exact_cost`` routes an assignment through the multi-job DES
  (:func:`repro.cluster.sched.simulate_workload`), the trusted reference —
  rows the wave model could not converge (``valid == 0``) are re-costed
  there by the standard escape hatch, never reported as a silent number.
  A workload that cannot finish on the candidate cluster raises
  :class:`UnfinishedWorkloadError` instead of returning an inf latency
  (the PR-2 no-silent-inf policy).

Override keys (the ``base_cfg`` universe, declared in :func:`cluster_space`):

  ``pNumNodes``, ``pMaxMapsPerNode``, ``pMaxRedPerNode``,
  ``pReduceSlowstart``, ``schedFair`` (legacy 0 = FIFO, 1 = fair),
  ``arrivalRate`` (jobs/s offered to the cluster),
  ``pNumFastNodes`` / ``fastSpeedup`` (the fleet mix: that many nodes run
  their compute ``fastSpeedup`` x faster, the rest are baseline),
  ``schedPolicy`` (0 = fifo, 1 = fair, 2 = fair_preempt, 3 = capacity;
  overrides ``schedFair`` when nonzero), ``preemptTimeout`` (DES grace
  seconds before an over-share kill; the wave model preempts at event
  boundaries, so this knob only moves ``exact_cost``),
  ``pNumRacks`` / ``crossRackBw`` / ``oversubscription`` (the network
  topology of :class:`repro.cluster.network.Topology`: ``pNumRacks=1`` or
  infinite ``crossRackBw`` is the flat network; otherwise shuffle flows
  contend for each rack's ``crossRackBw / oversubscription`` uplink —
  max-min fair-shared in the DES, count-approximated in the wave model).
"""

from __future__ import annotations

import functools
from typing import Any, Mapping, Sequence

import jax.numpy as jnp
import numpy as np

from repro import compat
from repro.core.hadoop.simulator import SimConfig
from repro.obs import current as _obs_current
from repro.search.evaluator import (
    Evaluator,
    ExactCostUnavailable,
    SearchResult,
    masked_total,
    pad_block,
    split_overrides,
)
from repro.spec import Axis, ParamSpace, Predicate

from .network import Topology
from .sched import ClusterConfig, NodeClass, simulate_workload
from .vector_sim import POLICIES, estimate_steps, pack_traces, simulate_batch
from .workload import JobClass, WorkloadTrace, default_job_classes, poisson_trace, rescale

__all__ = ["ClusterEvaluator", "UnfinishedWorkloadError", "cluster_space"]

_OBJECTIVES = {"mean": "w_meanLat", "p95": "w_p95Lat", "makespan": "w_makespan"}


class UnfinishedWorkloadError(ExactCostUnavailable):
    """The DES could not finish every job of the workload on this cluster
    (e.g. every node failed, or the trace outlives all slots) — the latency
    objective would be a silent ``inf``, so the evaluator raises instead.
    Subclasses :class:`repro.search.ExactCostUnavailable`, so the generic
    fallback paths (top-k, descent, service) skip the candidate with a log
    line instead of aborting a completed search."""


def _fast_fits_fleet(cols: Mapping[str, np.ndarray]) -> np.ndarray:
    """``pNumFastNodes <= pNumNodes`` — unconstrained when either column is
    absent from the masked batch (validity_mask accepts partial columns)."""
    if "pNumFastNodes" not in cols or "pNumNodes" not in cols:
        return np.asarray(True)
    return cols["pNumFastNodes"] <= cols["pNumNodes"]


def _racks_fit_fleet(cols: Mapping[str, np.ndarray]) -> np.ndarray:
    """``pNumRacks <= pNumNodes`` — an empty rack is a mis-specified
    topology, not a bigger cluster."""
    if "pNumRacks" not in cols or "pNumNodes" not in cols:
        return np.asarray(True)
    return cols["pNumRacks"] <= cols["pNumNodes"]


@functools.lru_cache(maxsize=None)
def cluster_space() -> ParamSpace:
    """The capacity planner's searchable axes (the ``base_cfg`` universe).

    The axis bounds ARE the planner's knob-validity rule: a row is valid
    when every (rounded) count is >= 1, the offered rate is positive, the
    fast-node count fits inside the fleet (``pNumFastNodes <= pNumNodes``,
    a cross-axis :class:`Predicate`), the fast class is at least baseline
    speed, and the policy code is one of the four schedulers — exactly the
    mask :meth:`ClusterEvaluator.evaluate` applies before the vectorized
    rollout.  ``pReduceSlowstart`` is a fraction and ``schedFair`` a flag;
    neither contributes a validity bound.
    """
    return ParamSpace([
        Axis("pNumNodes", kind="int", lower=1, table="Table 1",
             group="cluster", doc="worker nodes in the candidate cluster"),
        Axis("pMaxMapsPerNode", kind="int", lower=1, table="Table 1",
             group="cluster", doc="map slots per node"),
        Axis("pMaxRedPerNode", kind="int", lower=1, table="Table 1",
             group="cluster", doc="reduce slots per node"),
        Axis("pReduceSlowstart", kind="float", lower=None, unit="fraction",
             table="Table 1", group="cluster",
             doc="map completion fraction before reducers launch"),
        Axis("schedFair", kind="bool", group="cluster",
             doc="fair-share scheduler (0 = FIFO; legacy spelling of "
                 "schedPolicy=1)"),
        Axis("arrivalRate", kind="float", lower=0, lower_open=True,
             unit="jobs/s", group="cluster",
             doc="offered load the unit-rate trace is rescaled to"),
        Axis("pNumFastNodes", kind="int", lower=0, group="cluster",
             doc="nodes of the fast hardware class (rest are baseline)"),
        Axis("fastSpeedup", kind="float", lower=1, group="cluster",
             doc="compute speed factor of the fast class (>= baseline)"),
        Axis("schedPolicy", kind="int", lower=0, upper=3, group="cluster",
             doc="0 fifo | 1 fair | 2 fair_preempt | 3 capacity "
                 "(overrides schedFair when nonzero)"),
        Axis("preemptTimeout", kind="float", lower=0, unit="s",
             group="cluster",
             doc="grace before an over-share task is killed (DES only)"),
        Axis("pNumRacks", kind="int", lower=1, group="cluster",
             doc="racks the nodes are striped across (1 = flat network)"),
        Axis("crossRackBw", kind="float", lower=0, lower_open=True,
             unit="x nominal", group="cluster",
             doc="aggregate core-uplink bandwidth per rack, in units of one "
                 "flow's nominal rate (inf = never the bottleneck)"),
        Axis("oversubscription", kind="float", lower=1, group="cluster",
             doc="top-of-rack oversubscription factor dividing crossRackBw"),
    ], predicates=[
        Predicate(
            "fast nodes within fleet",
            _fast_fits_fleet,
            doc="the fast class cannot exceed the fleet size",
        ),
        Predicate(
            "racks within fleet",
            _racks_fit_fleet,
            doc="at least one node per rack",
        ),
    ])


class ClusterEvaluator(Evaluator):
    """Batched workload-on-cluster evaluation over candidate cluster configs.

    Parameters
    ----------
    classes : job mix (default :func:`default_job_classes`).
    traces : explicit unit-rate workload traces; default ``n_seeds`` Poisson
        traces of ``n_jobs`` jobs each.  The cost of a config is averaged
        over the traces, so one lucky arrival pattern cannot pick the
        cluster.
    base : cluster defaults for keys a query leaves alone (a heterogeneous
        ``node_classes`` base seeds ``pNumFastNodes``/``fastSpeedup``).
    base_rate : default offered load (jobs/s; ``arrivalRate`` override).
    capacities : capacity-scheduler guarantees, job-class name -> relative
        weight (normalized over the classes present in each trace; default
        equal shares) — used by both the wave model and the DES.
    sim : :class:`SimConfig` the DES (``exact_cost``) runs under — noise,
        speculation, node failures.  The wave model does not simulate
        failures; a failure schedule only moves the exact path.
    objective : ``"p95"`` (default — tail latency is what capacity is
        bought for), ``"mean"``, or ``"makespan"`` (the whole trace's
        elapsed time, first arrival to last finish).
    chunk : rows per vectorized call (rounded up to the device count).
    """

    def __init__(
        self,
        classes: Sequence[JobClass] | None = None,
        *,
        traces: Sequence[WorkloadTrace] | None = None,
        n_jobs: int = 32,
        n_seeds: int = 2,
        trace_seed: int = 0,
        base: ClusterConfig = ClusterConfig(),
        base_rate: float = 0.1,
        capacities: Mapping[str, float] | None = None,
        sim: SimConfig = SimConfig(),
        objective: str = "p95",
        chunk: int = 256,
        devices=None,
    ):
        if objective not in _OBJECTIVES:
            raise ValueError(f"objective must be one of {sorted(_OBJECTIVES)}")
        self.classes = list(classes) if classes is not None \
            else default_job_classes()
        self.traces = list(traces) if traces is not None else [
            poisson_trace(self.classes, n_jobs, rate=1.0, seed=trace_seed + s)
            for s in range(n_seeds)
        ]
        #: (S, J) per-job constants and (S, J, P) DAG edges shared by
        #: every scenario
        self._cols = pack_traces(self.traces)
        self._objective = objective
        self._base = base
        self._sim = sim
        self.capacities = dict(capacities) if capacities else {}
        # capacity-scheduler queues: one global name universe (evaluator
        # classes + any trace-only classes), per-trace guarantees normalized
        # over the classes PRESENT in that trace — the DES's rule, so
        # evaluate() and exact_cost() agree on what a guarantee means.
        qnames = sorted({jc.name for jc in self.classes}
                        | {a.klass.name for t in self.traces
                           for a in t.arrivals})
        qidx = {name: i for i, name in enumerate(qnames)}
        self._queue_cols = np.stack([
            np.asarray([qidx[a.klass.name] for a in t.arrivals], np.float64)
            for t in self.traces
        ])                                                      # (S, J)
        fracs = np.zeros((len(self.traces), len(qnames)))
        for s, t in enumerate(self.traces):
            present = sorted({a.klass.name for a in t.arrivals})
            w = {q: self.capacities.get(q, 1.0) for q in present}
            tot = sum(w.values()) or 1.0
            for q in present:
                fracs[s, qidx[q]] = w[q] / tot
        self._queue_fracs = fracs                               # (S, Q)
        self._devs = tuple(devices) if devices is not None \
            else tuple(compat.default_search_devices())
        self.num_devices = len(self._devs)
        self.chunk = -(-max(chunk, 1) // self.num_devices) * self.num_devices
        # a chunk's DAG edge columns (chunk x S, J, P), row-major like its
        # scenarios: the same for every row of a trace and every chunk, so
        # built once, as integers
        self._edge_cols = {k: np.tile(self._cols[k], (self.chunk, 1, 1))
                           for k in ("dep", "dep_kind")}
        fast_n, fast_spd = 0, 1.0
        if base.node_classes:
            # the axis space models a two-class fleet: N fast nodes
            # (speedup >= 1) + a unit-speed baseline — reject richer bases
            # instead of silently projecting them onto the wrong cluster
            fleet = sorted(base.node_classes, key=lambda nc: -nc.speedup)
            if (len(fleet) > 2 or fleet[-1].speedup < 1.0
                    or (len(fleet) == 2 and fleet[1].speedup != 1.0)):
                raise ValueError(
                    "ClusterEvaluator's pNumFastNodes/fastSpeedup axes model "
                    "a (fast + unit-speed baseline) fleet; base.node_classes "
                    f"= {base.node_classes} is not expressible — run richer "
                    "fleets through simulate_workload directly"
                )
            if fleet[0].speedup > 1.0:
                fast_n, fast_spd = fleet[0].count, fleet[0].speedup
        # strong-typed scalars (weak-typed defaults change the compile key
        # when an axis switches between scalar and batched-column form)
        fdt = jnp.result_type(float)
        self.base_cfg = {
            "pNumNodes": jnp.asarray(float(base.num_nodes), dtype=fdt),
            "pMaxMapsPerNode": jnp.asarray(
                float(base.map_slots_per_node), dtype=fdt),
            "pMaxRedPerNode": jnp.asarray(
                float(base.reduce_slots_per_node), dtype=fdt),
            "pReduceSlowstart": jnp.asarray(
                float(base.reduce_slowstart), dtype=fdt),
            "schedFair": jnp.asarray(
                1.0 if base.scheduler == "fair" else 0.0, dtype=fdt),
            "arrivalRate": jnp.asarray(float(base_rate), dtype=fdt),
            "pNumFastNodes": jnp.asarray(float(fast_n), dtype=fdt),
            "fastSpeedup": jnp.asarray(float(fast_spd), dtype=fdt),
            # fifo/fair bases seed schedPolicy=0 so the legacy schedFair
            # axis keeps full control (schedPolicy supersedes it when
            # nonzero); only the preemptive bases — which schedFair cannot
            # express — pin the policy code
            "schedPolicy": jnp.asarray(
                float(POLICIES.index(base.scheduler))
                if POLICIES.index(base.scheduler) >= 2 else 0.0, dtype=fdt),
            "preemptTimeout": jnp.asarray(
                float(base.preempt_timeout), dtype=fdt),
            "pNumRacks": jnp.asarray(
                float(base.topology.num_racks if base.topology else 1),
                dtype=fdt),
            "crossRackBw": jnp.asarray(
                float(base.topology.cross_rack_bw if base.topology
                      else float("inf")), dtype=fdt),
            "oversubscription": jnp.asarray(
                float(base.topology.oversub if base.topology else 1.0),
                dtype=fdt),
        }

    # ---------------- Evaluator interface ----------------

    @property
    def cost_key(self) -> str:
        return _OBJECTIVES[self._objective]

    @property
    def param_space(self) -> ParamSpace:
        """Declared cluster axes — the single source of the knob mask."""
        return cluster_space()

    def grad_objective(self):
        from repro.search.evaluator import NotDifferentiableError

        raise NotDifferentiableError(
            "cluster costs come from the discrete-event scheduler simulation "
            "(wave counts, preemption, arrival ordering) — piecewise-constant "
            "in every knob, so there is no useful gradient; gradient "
            "strategies fall back to coordinate descent here"
        )

    def evaluate(self, overrides: Mapping[str, Any]) -> SearchResult:
        with _obs_current().span("evaluator.prepare"):
            batched, static, n = split_overrides(self.base_cfg, overrides)
        out_blocks: dict[str, list[np.ndarray]] = {}
        for start in range(0, n, self.chunk):
            stop = min(start + self.chunk, n)
            rows, _ = pad_block(batched, start, stop, self.chunk)
            out = self._evaluate_rows(rows, static)
            for k, v in out.items():
                out_blocks.setdefault(k, []).append(v[: stop - start])
        outputs = {k: np.concatenate(v) for k, v in out_blocks.items()}
        total = masked_total(outputs, self.cost_key)
        return SearchResult(overrides=batched, outputs=outputs, total_cost=total)

    def _resolve_config(self, cfg: Mapping[str, float]) -> ClusterConfig | None:
        """A flat assignment -> :class:`ClusterConfig`, or ``None`` when the
        knobs violate the declared axis bounds / predicates."""
        nodes = int(round(cfg["pNumNodes"]))
        mpn = int(round(cfg["pMaxMapsPerNode"]))
        rpn = int(round(cfg["pMaxRedPerNode"]))
        fast = int(round(cfg["pNumFastNodes"]))
        fspd = float(cfg["fastSpeedup"])
        poli = int(round(cfg["schedPolicy"]))
        racks = int(round(cfg["pNumRacks"]))
        xbw = float(cfg["crossRackBw"])
        osub = float(cfg["oversubscription"])
        if poli == 0 and cfg["schedFair"] > 0.5:
            poli = 1                       # legacy boolean spelling
        if (nodes < 1 or mpn < 1 or rpn < 1 or cfg["arrivalRate"] <= 0
                or fast < 0 or fast > nodes or fspd < 1.0
                or not 0 <= poli < len(POLICIES)
                or cfg["preemptTimeout"] < 0
                or racks < 1 or racks > nodes or xbw <= 0 or osub < 1.0):
            return None
        fleet = ()
        if fast > 0 and fspd > 1.0:
            fleet = (NodeClass(fast, fspd),) + (
                (NodeClass(nodes - fast, 1.0),) if nodes > fast else ())
        topo = Topology(num_racks=racks, cross_rack_bw=xbw, oversub=osub) \
            if racks > 1 else None
        return ClusterConfig(
            num_nodes=nodes, map_slots_per_node=mpn, reduce_slots_per_node=rpn,
            scheduler=POLICIES[poli],
            reduce_slowstart=cfg["pReduceSlowstart"],
            node_classes=fleet,
            preempt_timeout=float(cfg["preemptTimeout"]),
            capacities=tuple(sorted(self.capacities.items())),
            topology=topo,
        )

    def exact_cost(self, assignment: Mapping[str, float]) -> float:
        """The multi-job DES on every trace; same objective, trusted path.

        Raises :class:`UnfinishedWorkloadError` when a trace cannot finish
        on the candidate cluster (the latency objective would be inf).
        """
        cfg = {k: float(np.asarray(v)) for k, v in self.base_cfg.items()}
        for k, v in assignment.items():
            if k not in cfg:
                raise KeyError(f"unknown config key: {k!r}")
            cfg[k] = float(v)
        cc = self._resolve_config(cfg)
        if cc is None:
            return float("inf")
        rate = cfg["arrivalRate"]
        vals = []
        for tr in self.traces:
            run = rescale(tr, rate)
            res = simulate_workload(run, cc, self._sim)
            if res.n_unfinished:
                raise UnfinishedWorkloadError(
                    f"{res.n_unfinished}/{len(res.jobs)} jobs never finished "
                    f"on {cc} — the {self._objective} objective is "
                    "undefined (inf); inspect WorkloadResult.n_unfinished"
                )
            if self._objective == "makespan":
                # the wave model's span: first arrival to last finish
                vals.append(res.makespan - run.arrivals[0].submit_time)
            else:
                vals.append(res.p95_latency if self._objective == "p95"
                            else res.mean_latency)
        return float(np.mean(vals))

    # ---------------- internals ----------------

    def _evaluate_rows(self, rows: Mapping[str, np.ndarray],
                       static: Mapping[str, float]) -> dict[str, np.ndarray]:
        """One padded chunk -> per-row metrics (row x trace scenarios)."""
        with _obs_current().span("cluster.build_scenarios"):
            scen, ok = self._scenarios(rows, static)
            n_steps = estimate_steps(scen)
        out = simulate_batch(scen, n_steps=n_steps, devices=self._devs)
        shp = (self.chunk, len(self.traces))
        mean_lat = out["mean_latency"].reshape(shp).mean(axis=1)
        p95_lat = out["p95_latency"].reshape(shp).mean(axis=1)
        conv = out["converged"].reshape(shp).min(axis=1)
        return {
            "w_meanLat": mean_lat.astype(np.float64),
            "w_p95Lat": p95_lat.astype(np.float64),
            "w_makespan": out["makespan"].reshape(shp).mean(axis=1).astype(np.float64),
            "w_util": out["utilization"].reshape(shp).mean(axis=1).astype(np.float64),
            "valid": (ok & (conv > 0)).astype(np.float64),
        }

    def _scenarios(self, rows: Mapping[str, np.ndarray],
                   static: Mapping[str, float]) -> tuple[dict, np.ndarray]:
        """One padded chunk's scenario batch (row-major, one scenario per
        row and trace) and the rows' knob-validity mask."""
        b = self.chunk
        col = lambda k: rows[k] if k in rows else np.full(b, static[k])
        nodes = np.round(col("pNumNodes"))
        mpn = np.round(col("pMaxMapsPerNode"))
        rpn = np.round(col("pMaxRedPerNode"))
        rate = col("arrivalRate")
        fair = (col("schedFair") > 0.5).astype(np.float64)
        slow = col("pReduceSlowstart")
        fast = np.round(col("pNumFastNodes"))
        fspd = col("fastSpeedup")
        polx = np.round(col("schedPolicy"))
        # schedPolicy supersedes the legacy boolean when nonzero
        pol = np.where(polx > 0, polx, fair)
        # the declared axis bounds + predicates (counts >= 1, rate > 0,
        # fast class inside the fleet, speedup >= 1, policy code in range)
        # ARE the mask
        ok, _ = self.param_space.validity_mask(
            {k: col(k) for k in self.base_cfg})
        # invalid rows are masked via ``ok``, but still ride the vmapped
        # rollout — sanitize their knobs so a zero-slot lane cannot pin the
        # whole chunk at the step cap (a lane that never finishes keeps the
        # while_loop running for everyone)
        nodes_s = np.maximum(nodes, 1.0)
        mpn_s = np.maximum(mpn, 1.0)
        rpn_s = np.maximum(rpn, 1.0)
        rate_s = np.where(rate > 0, rate, 1.0)
        fast_s = np.clip(fast, 0.0, nodes_s)
        fspd_s = np.maximum(fspd, 1.0)
        pol_s = np.clip(pol, 0.0, float(len(POLICIES) - 1))
        base_n = nodes_s - fast_s
        racks = np.round(col("pNumRacks"))
        xbw = col("crossRackBw")
        osub = col("oversubscription")
        racks_s = np.clip(racks, 1.0, nodes_s)
        xbw_s = np.where(xbw > 0, xbw, np.inf)
        osub_s = np.maximum(osub, 1.0)

        cols, s = self._cols, len(self.traces)
        rep = lambda a: np.repeat(a[:, None], s, axis=1).reshape(b * s)
        rep2 = lambda a: np.repeat(a, s, axis=0)        # (b, C) -> (b*s, C)
        perjob = lambda a: np.broadcast_to(
            a[None], (b,) + a.shape).reshape(b * s, -1)
        frac = (nodes_s - 1.0) / nodes_s
        scen = {
            "arrival": perjob(cols["arrival"]) / rep(rate_s)[:, None],
            "n_maps": perjob(cols["n_maps"]),
            "n_reds": perjob(cols["n_reds"]),
            "map_cost": perjob(cols["map_cost"]),
            "red_work": perjob(cols["red_work"]),
            "shuffle": perjob(cols["shuffle"]) * rep(frac)[:, None],
            "policy": rep(pol_s),
            "slowstart": rep(slow),
            "queue": perjob(self._queue_cols),
            "queue_frac": np.tile(self._queue_fracs, (b, 1)),
            "topo_racks": rep(racks_s),
            "topo_cross_bw": rep(xbw_s),
            "topo_oversub": rep(osub_s),
        }
        scen.update(self._edge_cols)
        if np.any(fast_s > 0):
            # two class columns, fastest first: (fast fleet, baseline fleet)
            scen["map_slots"] = rep2(np.stack(
                [fast_s * mpn_s, base_n * mpn_s], 1))
            scen["red_slots"] = rep2(np.stack(
                [fast_s * rpn_s, base_n * rpn_s], 1))
            scen["speedup"] = rep2(np.stack(
                [fspd_s, np.ones_like(fspd_s)], axis=1))
        else:
            # all-homogeneous chunk: 1-D slot columns keep the lean
            # one-class kernel (no per-class wave state)
            scen["map_slots"] = rep(nodes_s * mpn_s)
            scen["red_slots"] = rep(nodes_s * rpn_s)
        return scen, ok
