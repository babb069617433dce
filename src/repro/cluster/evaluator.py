"""Capacity planning behind the :class:`repro.search.Evaluator` interface.

:class:`WavePlanner` is the core of both capacity planners, this module's
:class:`ClusterEvaluator` and :class:`repro.cloud.CloudEvaluator`:

* ``evaluate`` expands each override row into (row x workload-seed)
  scenarios, rolls them out with the vectorized wave simulator
  (:mod:`repro.cluster.vector_sim`), and reduces each row's scenarios to
  the planner's metrics;
* ``exact_cost`` routes an assignment through the multi-job DES
  (:func:`repro.cluster.sched.simulate_workload`), the trusted reference —
  rows the wave model could not converge (``valid == 0``) are re-costed
  there by the standard escape hatch, never reported as a silent number.
  A workload that cannot finish on the candidate cluster raises
  :class:`UnfinishedWorkloadError` instead of returning a silent inf
  cost.

A planner adds its fleet axes to the shared ones (:func:`planner_axes`)
and fills four hooks: the fleet's scenario columns, an assignment's
cluster, the reduction of a chunk's rollout and that of the DES runs.

``ClusterEvaluator`` makes *cluster* knobs — node count, fleet mix, slots
per node, scheduler policy, preemption, reduce slowstart, offered arrival
rate — searchable by every existing strategy (``grid_search_ev``,
``random_search_ev``, ``coordinate_descent_ev``, streaming
``search_topk``) and servable by :class:`repro.search.WhatIfService`,
exactly like the single-job Hadoop model.  Its cost is ``mean`` or
``p95`` job latency (submit -> finish), or the ``makespan`` (first arrival
-> last finish, the elapsed time of a batch such as a benchmark's
throughput test), averaged over the workload seeds.

Override keys (the ``base_cfg`` universe, declared in :func:`cluster_space`):

  ``pNumNodes``, ``pMaxMapsPerNode``, ``pMaxRedPerNode``,
  ``pReduceSlowstart``, ``arrivalRate`` (jobs/s offered to the cluster),
  ``pNumFastNodes`` / ``fastSpeedup`` (the fleet mix: that many nodes run
  their compute ``fastSpeedup`` x faster, the rest are baseline),
  ``schedPolicy`` (0 = fifo, 1 = fair, 2 = fair_preempt, 3 = capacity),
  ``preemptTimeout`` (DES grace seconds before an over-share kill; the
  wave model preempts at event boundaries, so this knob only moves
  ``exact_cost``), ``pNumRacks`` / ``crossRackBw`` / ``oversubscription``
  (the network topology of :class:`repro.cluster.network.Topology`:
  ``pNumRacks=1`` or infinite ``crossRackBw`` is the flat network;
  otherwise shuffle flows contend for each rack's ``crossRackBw /
  oversubscription`` uplink — max-min fair-shared in the DES,
  count-approximated in the wave model).
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
    NotDifferentiableError,
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

__all__ = ["ClusterEvaluator", "UnfinishedWorkloadError", "WavePlanner",
           "cluster_space", "planner_axes", "racks_within_fleet"]

_OBJECTIVES = {"mean": "w_meanLat", "p95": "w_p95Lat", "makespan": "w_makespan"}


class UnfinishedWorkloadError(ExactCostUnavailable):
    """The DES could not finish every job of the workload on this cluster
    (e.g. every node failed, or the trace outlives all slots) — the
    objective would be a silent ``inf``, so the evaluator raises instead.
    Subclasses :class:`repro.search.ExactCostUnavailable`, so the generic
    fallback paths (top-k, descent, service) skip the candidate with a log
    line instead of aborting a completed search."""


def planner_axes(group: str) -> dict[str, Axis]:
    """The axes every wave planner declares, by name: slots per node,
    reduce slowstart, offered load, scheduler policy and the rack
    topology.  A planner's :class:`ParamSpace` lists them among its fleet
    axes, in its own order."""
    return {ax.name: ax for ax in (
        Axis("pMaxMapsPerNode", kind="int", lower=1, table="Table 1",
             group=group, doc="map slots per node"),
        Axis("pMaxRedPerNode", kind="int", lower=1, table="Table 1",
             group=group, doc="reduce slots per node"),
        Axis("pReduceSlowstart", kind="float", lower=None, unit="fraction",
             table="Table 1", group=group,
             doc="map completion fraction before reducers launch"),
        Axis("arrivalRate", kind="float", lower=0, lower_open=True,
             unit="jobs/s", group=group,
             doc="offered load the unit-rate trace is rescaled to"),
        Axis("schedPolicy", kind="int", lower=0, upper=3, group=group,
             doc="0 fifo | 1 fair | 2 fair_preempt | 3 capacity"),
        Axis("pNumRacks", kind="int", lower=1, group=group,
             doc="racks the nodes are striped across (1 = flat network)"),
        Axis("crossRackBw", kind="float", lower=0, lower_open=True,
             unit="x nominal", group=group,
             doc="aggregate core-uplink bandwidth per rack, in units of one "
                 "flow's nominal rate (inf = never the bottleneck)"),
        Axis("oversubscription", kind="float", lower=1, group=group,
             doc="top-of-rack oversubscription factor dividing crossRackBw"),
    )}


def racks_within_fleet(*fleet_axes: str) -> Predicate:
    """``pNumRacks`` at most the fleet's node total, the sum of the
    ``fleet_axes`` columns — an empty rack is a mis-specified topology,
    not a bigger cluster.  Unconstrained when a column is absent from the
    masked batch (validity_mask accepts partial columns)."""
    def fits(cols: Mapping[str, np.ndarray]) -> np.ndarray:
        if any(k not in cols for k in ("pNumRacks",) + fleet_axes):
            return np.asarray(True)
        return cols["pNumRacks"] <= sum(cols[k] for k in fleet_axes)

    return Predicate("racks within fleet", fits,
                     doc="at least one node per rack")


def _fast_fits_fleet(cols: Mapping[str, np.ndarray]) -> np.ndarray:
    """``pNumFastNodes <= pNumNodes`` — unconstrained when either column is
    absent from the masked batch (validity_mask accepts partial columns)."""
    if "pNumFastNodes" not in cols or "pNumNodes" not in cols:
        return np.asarray(True)
    return cols["pNumFastNodes"] <= cols["pNumNodes"]


@functools.lru_cache(maxsize=None)
def cluster_space() -> ParamSpace:
    """The capacity planner's searchable axes (the ``base_cfg`` universe).

    The axis bounds ARE the planner's knob-validity rule: a row is valid
    when every (rounded) count is >= 1, the offered rate is positive, the
    fast-node count fits inside the fleet (``pNumFastNodes <= pNumNodes``,
    a cross-axis :class:`Predicate`), the fast class is at least baseline
    speed, and the policy code is one of the four schedulers — exactly the
    mask :meth:`ClusterEvaluator.evaluate` applies before the vectorized
    rollout.  ``pReduceSlowstart`` is a fraction and contributes no
    validity bound.
    """
    shared = planner_axes("cluster")
    return ParamSpace([
        Axis("pNumNodes", kind="int", lower=1, table="Table 1",
             group="cluster", doc="worker nodes in the candidate cluster"),
        shared["pMaxMapsPerNode"],
        shared["pMaxRedPerNode"],
        shared["pReduceSlowstart"],
        shared["arrivalRate"],
        Axis("pNumFastNodes", kind="int", lower=0, group="cluster",
             doc="nodes of the fast hardware class (rest are baseline)"),
        Axis("fastSpeedup", kind="float", lower=1, group="cluster",
             doc="compute speed factor of the fast class (>= baseline)"),
        shared["schedPolicy"],
        Axis("preemptTimeout", kind="float", lower=0, unit="s",
             group="cluster",
             doc="grace before an over-share task is killed (DES only)"),
        shared["pNumRacks"],
        shared["crossRackBw"],
        shared["oversubscription"],
    ], predicates=[
        Predicate(
            "fast nodes within fleet",
            _fast_fits_fleet,
            doc="the fast class cannot exceed the fleet size",
        ),
        racks_within_fleet("pNumNodes"),
    ])


class WavePlanner(Evaluator):
    """Batched workload-on-cluster evaluation over candidate clusters: the
    core a capacity planner fills with its fleet.

    Parameters
    ----------
    classes : job mix (default :func:`default_job_classes`).
    traces : explicit unit-rate workload traces; default ``n_seeds`` Poisson
        traces of ``n_jobs`` jobs each.  The cost of a config is averaged
        over the traces, so one lucky arrival pattern cannot pick the
        cluster.
    base : cluster defaults for keys a query leaves alone.
    base_rate : default offered load (jobs/s; ``arrivalRate`` override).
    capacities : capacity-scheduler guarantees, job-class name -> relative
        weight (normalized over the classes present in each trace; default
        equal shares) — used by both the wave model and the DES.
    sim : :class:`SimConfig` the DES (``exact_cost``) runs under — noise,
        speculation, node failures.  The wave model does not simulate
        failures; a failure schedule only moves the exact path.
    chunk : rows per vectorized call (rounded up to the device count).
    fleet : the base's value of each of the planner's own axes.

    A subclass declares :attr:`param_space` and :attr:`cost_key` and fills
    :meth:`_fleet`, :meth:`_resolve_config`, :meth:`_reduce` and
    :meth:`_exact_value`.
    """

    def __init__(
        self,
        classes: Sequence[JobClass] | None,
        *,
        traces: Sequence[WorkloadTrace] | None,
        n_jobs: int,
        n_seeds: int,
        trace_seed: int,
        base: ClusterConfig,
        base_rate: float,
        capacities: Mapping[str, float] | None,
        sim: SimConfig,
        chunk: int,
        devices,
        fleet: Mapping[str, float],
    ):
        self.classes = list(classes) if classes is not None \
            else default_job_classes()
        self.traces = list(traces) if traces is not None else [
            poisson_trace(self.classes, n_jobs, rate=1.0, seed=trace_seed + s)
            for s in range(n_seeds)
        ]
        #: (S, J) per-job constants and (S, J, P) DAG edges shared by
        #: every scenario
        self._cols = pack_traces(self.traces)
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
        topo = base.topology
        seeds = {
            "pMaxMapsPerNode": base.map_slots_per_node,
            "pMaxRedPerNode": base.reduce_slots_per_node,
            "pReduceSlowstart": base.reduce_slowstart,
            "arrivalRate": base_rate,
            "schedPolicy": POLICIES.index(base.scheduler),
            "pNumRacks": topo.num_racks if topo else 1,
            "crossRackBw": topo.cross_rack_bw if topo else float("inf"),
            "oversubscription": topo.oversub if topo else 1.0,
            **fleet,
        }
        # strong-typed scalars (weak-typed defaults change the compile key
        # when an axis switches between scalar and batched-column form)
        fdt = jnp.result_type(float)
        self.base_cfg = {k: jnp.asarray(float(seeds[k]), dtype=fdt)
                         for k in self.param_space.names}

    # ---------------- Evaluator interface ----------------

    def grad_objective(self):
        raise NotDifferentiableError(
            "planner costs come from the wave rollout and the discrete-event "
            "scheduler simulation (wave counts, preemption, arrival "
            "ordering, reclaim and provision events) — piecewise-constant "
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

    def exact_cost(self, assignment: Mapping[str, float]) -> float:
        """The multi-job DES on every trace; same objective, trusted path.

        Raises :class:`UnfinishedWorkloadError` when a trace cannot finish
        on the candidate cluster (the objective would be inf).
        """
        cfg = {k: float(np.asarray(v)) for k, v in self.base_cfg.items()}
        for k, v in assignment.items():
            if k not in cfg:
                raise KeyError(f"unknown config key: {k!r}")
            cfg[k] = float(v)
        resolved = self._resolve_config(cfg)
        if resolved is None:
            return float("inf")
        cc, el = resolved
        runs = []
        for tr in self.traces:
            run = rescale(tr, cfg["arrivalRate"])
            res = simulate_workload(run, cc, self._sim, elastic=el)
            if res.n_unfinished:
                raise UnfinishedWorkloadError(
                    f"{res.n_unfinished}/{len(res.jobs)} jobs never finished "
                    f"on {cc} — the {self.cost_key} objective is undefined "
                    "(inf); inspect WorkloadResult.n_unfinished"
                )
            runs.append((run, res))
        return self._exact_value(cfg, cc, el, runs)

    # ---------------- the planner's hooks ----------------

    def _fleet(self, col, mpn: np.ndarray, rpn: np.ndarray):
        """The fleet's part of a chunk, from its knob columns ``col(key)``
        and its sanitized slots per node: the sanitized node total (b,),
        the fleet's scenario columns, one (b,) or (b, C) row per grid
        row, and what :meth:`_reduce` needs of them."""
        raise NotImplementedError

    def _resolve_config(self, cfg: Mapping[str, float]):
        """A flat assignment -> ``(ClusterConfig, ElasticFleet | None)``, or
        ``None`` when the knobs violate the declared axis bounds /
        predicates (:meth:`_cluster_config` checks the shared ones)."""
        raise NotImplementedError

    def _reduce(self, out: Mapping[str, np.ndarray], fleet) -> dict[str, np.ndarray]:
        """A chunk's rollout (row x trace scenarios) -> per-row metrics,
        ``valid`` aside; ``fleet`` is what :meth:`_fleet` kept."""
        raise NotImplementedError

    def _exact_value(self, cfg, cc, el, runs) -> float:
        """The DES's ``(rescaled trace, WorkloadResult)`` of every trace ->
        the assignment's cost."""
        raise NotImplementedError

    # ---------------- internals ----------------

    def _cluster_config(self, cfg: Mapping[str, float], num_nodes: int,
                        node_classes: tuple, **fields) -> ClusterConfig | None:
        """The cluster of an assignment on a fleet of ``num_nodes`` nodes,
        or ``None`` when a shared knob violates its bounds."""
        mpn = int(round(cfg["pMaxMapsPerNode"]))
        rpn = int(round(cfg["pMaxRedPerNode"]))
        poli = int(round(cfg["schedPolicy"]))
        racks = int(round(cfg["pNumRacks"]))
        xbw = float(cfg["crossRackBw"])
        osub = float(cfg["oversubscription"])
        if (mpn < 1 or rpn < 1 or cfg["arrivalRate"] <= 0
                or not 0 <= poli < len(POLICIES)
                or racks < 1 or racks > num_nodes or xbw <= 0 or osub < 1.0):
            return None
        return ClusterConfig(
            num_nodes=num_nodes,
            map_slots_per_node=mpn, reduce_slots_per_node=rpn,
            scheduler=POLICIES[poli],
            reduce_slowstart=float(cfg["pReduceSlowstart"]),
            node_classes=node_classes,
            capacities=tuple(sorted(self.capacities.items())),
            topology=Topology(num_racks=racks, cross_rack_bw=xbw,
                              oversub=osub) if racks > 1 else None,
            **fields,
        )

    def _per_row(self, out: Mapping[str, np.ndarray], key: str) -> np.ndarray:
        """A per-scenario metric averaged over each row's traces."""
        shp = (self.chunk, len(self.traces))
        return out[key].reshape(shp).mean(axis=1).astype(np.float64)

    def _evaluate_rows(self, rows: Mapping[str, np.ndarray],
                       static: Mapping[str, float]) -> dict[str, np.ndarray]:
        """One padded chunk -> per-row metrics (row x trace scenarios)."""
        with _obs_current().span("cluster.build_scenarios"):
            scen, ok, fleet = self._scenarios(rows, static)
            n_steps = estimate_steps(scen)
        out = simulate_batch(scen, n_steps=n_steps, devices=self._devs)
        conv = out["converged"].reshape(self.chunk, len(self.traces)).min(axis=1)
        return {**self._reduce(out, fleet),
                "valid": (ok & (conv > 0)).astype(np.float64)}

    def _scenarios(self, rows: Mapping[str, np.ndarray],
                   static: Mapping[str, float]) -> tuple[dict, np.ndarray, Any]:
        """One padded chunk's scenario batch (row-major, one scenario per
        row and trace), the rows' knob-validity mask, and what the fleet
        keeps for :meth:`_reduce`."""
        b, s = self.chunk, len(self.traces)
        col = lambda k: rows[k] if k in rows else np.full(b, static[k])
        # the declared axis bounds + predicates ARE the mask
        ok, _ = self.param_space.validity_mask(
            {k: col(k) for k in self.base_cfg})
        # invalid rows are masked via ``ok``, but still ride the vmapped
        # rollout — sanitize their knobs so a zero-slot lane cannot pin the
        # whole chunk at the step cap (a lane that never finishes keeps the
        # while_loop running for everyone)
        mpn = np.maximum(np.round(col("pMaxMapsPerNode")), 1.0)
        rpn = np.maximum(np.round(col("pMaxRedPerNode")), 1.0)
        nodes, fleet_cols, fleet = self._fleet(col, mpn, rpn)
        rate = col("arrivalRate")
        # float64 whatever the knobs' precision, like the per-job columns
        pol = np.clip(np.round(col("schedPolicy")), 0.0,
                      float(len(POLICIES) - 1)).astype(np.float64)
        xbw = col("crossRackBw")

        cols = self._cols
        rep = lambda a: np.repeat(a, s, axis=0)     # (b, ...) -> (b*s, ...)
        perjob = lambda a: np.broadcast_to(
            a[None], (b,) + a.shape).reshape(b * s, -1)
        frac = (nodes - 1.0) / nodes
        scen = {
            "arrival": perjob(cols["arrival"])
            / rep(np.where(rate > 0, rate, 1.0))[:, None],
            "n_maps": perjob(cols["n_maps"]),
            "n_reds": perjob(cols["n_reds"]),
            "map_cost": perjob(cols["map_cost"]),
            "red_work": perjob(cols["red_work"]),
            "shuffle": perjob(cols["shuffle"]) * rep(frac)[:, None],
            "policy": rep(pol),
            "slowstart": rep(col("pReduceSlowstart")),
            "queue": perjob(self._queue_cols),
            "queue_frac": np.tile(self._queue_fracs, (b, 1)),
            "topo_racks": rep(np.clip(np.round(col("pNumRacks")), 1.0, nodes)),
            "topo_cross_bw": rep(np.where(xbw > 0, xbw, np.inf)),
            "topo_oversub": rep(np.maximum(col("oversubscription"), 1.0)),
        }
        scen.update(self._edge_cols)
        scen.update({k: rep(v) for k, v in fleet_cols.items()})
        return scen, ok, fleet


class ClusterEvaluator(WavePlanner):
    """Batched workload-on-cluster evaluation over candidate cluster configs.

    Parameters are :class:`WavePlanner`'s but ``fleet``, and:

    base : also seeds ``pNumFastNodes``/``fastSpeedup`` from a
        heterogeneous ``node_classes`` base (a fast class and a unit-speed
        baseline).
    objective : ``"p95"`` (default — tail latency is what capacity is
        bought for), ``"mean"``, or ``"makespan"`` (the whole trace's
        elapsed time, first arrival to last finish).
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
        self._objective = objective
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
        super().__init__(
            classes, traces=traces, n_jobs=n_jobs, n_seeds=n_seeds,
            trace_seed=trace_seed, base=base, base_rate=base_rate,
            capacities=capacities, sim=sim, chunk=chunk, devices=devices,
            fleet={"pNumNodes": base.num_nodes, "pNumFastNodes": fast_n,
                   "fastSpeedup": fast_spd,
                   "preemptTimeout": base.preempt_timeout})

    @property
    def cost_key(self) -> str:
        return _OBJECTIVES[self._objective]

    @property
    def param_space(self) -> ParamSpace:
        """Declared cluster axes — the single source of the knob mask."""
        return cluster_space()

    def _fleet(self, col, mpn, rpn):
        nodes = np.maximum(np.round(col("pNumNodes")), 1.0)
        fast = np.clip(np.round(col("pNumFastNodes")), 0.0, nodes)
        if not np.any(fast > 0):
            # all-homogeneous chunk: 1-D slot columns keep the lean
            # one-class kernel (no per-class wave state)
            return nodes, {"map_slots": nodes * mpn,
                           "red_slots": nodes * rpn}, None
        fspd = np.maximum(col("fastSpeedup"), 1.0)
        base_n = nodes - fast
        # two class columns, fastest first: (fast fleet, baseline fleet)
        return nodes, {
            "map_slots": np.stack([fast * mpn, base_n * mpn], 1),
            "red_slots": np.stack([fast * rpn, base_n * rpn], 1),
            "speedup": np.stack([fspd, np.ones_like(fspd)], axis=1),
        }, None

    def _resolve_config(self, cfg):
        nodes = int(round(cfg["pNumNodes"]))
        fast = int(round(cfg["pNumFastNodes"]))
        fspd = float(cfg["fastSpeedup"])
        if (nodes < 1 or fast < 0 or fast > nodes or fspd < 1.0
                or cfg["preemptTimeout"] < 0):
            return None
        fleet = ()
        if fast > 0 and fspd > 1.0:
            fleet = (NodeClass(fast, fspd),) + (
                (NodeClass(nodes - fast, 1.0),) if nodes > fast else ())
        cc = self._cluster_config(
            cfg, nodes, fleet, preempt_timeout=float(cfg["preemptTimeout"]))
        return None if cc is None else (cc, None)

    def _reduce(self, out, fleet):
        return {
            "w_meanLat": self._per_row(out, "mean_latency"),
            "w_p95Lat": self._per_row(out, "p95_latency"),
            "w_makespan": self._per_row(out, "makespan"),
            "w_util": self._per_row(out, "utilization"),
        }

    def _exact_value(self, cfg, cc, el, runs):
        if self._objective == "makespan":
            # the wave model's span: first arrival to last finish
            vals = [res.makespan - run.arrivals[0].submit_time
                    for run, res in runs]
        elif self._objective == "p95":
            vals = [res.p95_latency for _, res in runs]
        else:
            vals = [res.mean_latency for _, res in runs]
        return float(np.mean(vals))
