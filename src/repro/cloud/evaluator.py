"""Price/performance capacity planning behind the ``Evaluator`` interface.

``CloudEvaluator`` is the economic layer on top of the cluster planner:
the same workload-on-cluster rollout (wave simulator batched, DES exact),
run by the same core (:class:`~repro.cluster.evaluator.WavePlanner`), but
the objective is **dollars per job under an SLO** instead of latency
at a fixed fleet.  Because it implements :class:`repro.search.Evaluator`,
every strategy (``grid_search_ev``, ``random_search_ev``,
``coordinate_descent_ev``, streaming ``search_topk``) and
:class:`repro.search.WhatIfService` walk the price-performance Pareto
frontier unchanged.

Override keys (the ``base_cfg`` universe, declared in :func:`cloud_space`):

  ``pOnDemandNodes`` / ``pSpotNodes`` — the priced two-class fleet (spot
  first; both classes run at baseline speed, they differ in price and
  reclaimability), ``spotReclaimRate`` (1/s exponential reclamation of
  spot capacity), ``autoscalePolicy`` / ``autoscaleHighWater`` (the
  :data:`~repro.cloud.autoscaler.AUTOSCALE_POLICIES` code and its
  scale-up trigger), ``sloLatency`` (per-job latency bound the fleet is
  bought to meet), ``pNumRacks`` / ``crossRackBw`` / ``oversubscription``
  (the :class:`repro.cluster.network.Topology` the fleet is wired with —
  racks=1 or infinite bandwidth is the flat network), plus the familiar
  ``pMaxMapsPerNode``, ``pMaxRedPerNode``, ``pReduceSlowstart``,
  ``schedPolicy`` and ``arrivalRate`` cluster knobs.

Cost semantics:

* ``c_cost`` (the search objective) is mean dollars-per-job when the
  workload's SLO attainment reaches ``slo_target``, else ``inf`` — an
  SLO-infeasible fleet is never "cheap", it is not a candidate.
* ``evaluate`` prices the wave rollout: base fleet billed over the
  workload span, autoscaled extras over their ``extra_billed_s``
  episodes, spot reclamation folded into task durations in expectation
  (:func:`~repro.cloud.pricing.spot_inflation` inside the simulator).
* ``exact_cost`` runs the DES with the real reclaim/provision event
  processes and bills the recorded per-node online episodes
  (:func:`~repro.cloud.pricing.bill_workload`).  A workload that cannot
  finish raises ``UnfinishedWorkloadError``; a workload that finishes
  but misses the SLO raises :class:`SloUnmetError` — both subclass
  :class:`repro.search.ExactCostUnavailable`, so fallback paths skip
  the candidate loudly instead of reporting a silent number.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Mapping, Sequence

import numpy as np

from repro.core.hadoop.simulator import SimConfig
from repro.search.evaluator import ExactCostUnavailable, SearchResult
from repro.spec import Axis, ParamSpace, Predicate, ProvisioningReport

from repro.cluster.evaluator import WavePlanner, planner_axes, racks_within_fleet
from repro.cluster.sched import ClusterConfig, NodeClass
from repro.cluster.workload import JobClass, WorkloadTrace

from .autoscaler import AUTOSCALE_POLICIES, ElasticFleet
from .pricing import bill_workload

__all__ = ["CloudEvaluator", "SloUnmetError", "cloud_space"]

_SLO_EPS = 1e-9


class SloUnmetError(ExactCostUnavailable):
    """The DES finished the workload but its SLO attainment fell short of
    the evaluator's ``slo_target`` — dollars-per-job is defined but the
    fleet is not a feasible candidate, so ``exact_cost`` raises instead of
    returning a cost the search could mistake for cheap.  Subclasses
    :class:`repro.search.ExactCostUnavailable`: generic fallback paths
    (top-k, descent, service) skip the candidate with a log line."""


def _fleet_has_nodes(cols: Mapping[str, np.ndarray]) -> np.ndarray:
    """``pOnDemandNodes + pSpotNodes >= 1`` — someone must run the work;
    unconstrained when either column is absent (validity_mask accepts
    partial columns)."""
    if "pOnDemandNodes" not in cols or "pSpotNodes" not in cols:
        return np.asarray(True)
    return (np.round(cols["pOnDemandNodes"])
            + np.round(cols["pSpotNodes"])) >= 1


def _reclaim_needs_spot(cols: Mapping[str, np.ndarray]) -> np.ndarray:
    """A positive ``spotReclaimRate`` with zero spot nodes is a nonsense
    config (the reclaim process has nothing to act on) — masked instead of
    silently ignored.  Spot nodes with rate 0 stay valid: cheap capacity
    that happens never to be reclaimed."""
    if "spotReclaimRate" not in cols or "pSpotNodes" not in cols:
        return np.asarray(True)
    return (cols["spotReclaimRate"] <= 0) | (np.round(cols["pSpotNodes"]) > 0)


@functools.lru_cache(maxsize=None)
def cloud_space() -> ParamSpace:
    """The elastic capacity planner's searchable axes.

    The bounds ARE the feasibility rule: node counts >= 0 with at least
    one node total (a cross-axis :class:`Predicate`), slots >= 1, a
    positive offered rate, reclaim rate >= 0 (and only meaningful with
    spot capacity — the second predicate), a policy code in range for
    both the scheduler and the autoscaler, and a positive SLO bound.
    """
    shared = planner_axes("cloud")
    return ParamSpace([
        Axis("pOnDemandNodes", kind="int", lower=0, group="cloud",
             doc="on-demand (never reclaimed) nodes in the priced fleet"),
        Axis("pSpotNodes", kind="int", lower=0, group="cloud",
             doc="spot (reclaimable, cheaper) nodes in the priced fleet"),
        shared["pMaxMapsPerNode"],
        shared["pMaxRedPerNode"],
        shared["pReduceSlowstart"],
        shared["arrivalRate"],
        shared["schedPolicy"],
        Axis("spotReclaimRate", kind="float", lower=0, unit="1/s",
             group="cloud",
             doc="exponential reclaim rate of every spot node (0 = never)"),
        Axis("autoscalePolicy", kind="int", lower=0, upper=2, group="cloud",
             doc="0 off | 1 queue (high-water trigger) | 2 predicted "
                 "(provision up front)"),
        Axis("autoscaleHighWater", kind="float", lower=0, unit="slots",
             group="cloud",
             doc="unmet-demand slots that trigger the queue policy"),
        Axis("sloLatency", kind="float", lower=0, lower_open=True, unit="s",
             group="cloud",
             doc="per-job latency bound; attainment is the fraction of "
                 "jobs at or under it"),
        shared["pNumRacks"],
        shared["crossRackBw"],
        shared["oversubscription"],
    ], predicates=[
        Predicate("fleet has nodes", _fleet_has_nodes,
                  doc="on-demand + spot node count must be >= 1"),
        Predicate("reclaim rate needs spot capacity", _reclaim_needs_spot,
                  doc="a positive spotReclaimRate requires spot nodes"),
        racks_within_fleet("pOnDemandNodes", "pSpotNodes"),
    ])


class CloudEvaluator(WavePlanner):
    """Batched dollars-under-SLO evaluation over candidate priced fleets.

    Parameters
    ----------
    classes / traces / n_jobs / n_seeds / trace_seed / base_rate /
        capacities / sim / chunk : the workload and the planner, as in
        :class:`~repro.cluster.evaluator.WavePlanner` — cost is averaged
        over the traces.
    base : cluster defaults for the non-priced knobs (slots, scheduler,
        slowstart).  Must be a homogeneous base (no ``node_classes``) —
        the fleet mix is what the price axes search over.
    on_demand_price / spot_price : $/hour per node of each class.
    elastic : provisioning lifecycle + autoscaler defaults
        (:class:`~repro.cloud.autoscaler.ElasticFleet`); the
        ``autoscalePolicy`` / ``autoscaleHighWater`` / ``spotReclaimRate``
        axes override its policy, trigger and rate per candidate.  Extra
        nodes bill at ``elastic.extra_hourly_price``, default the
        on-demand price.
    slo_target : required SLO attainment fraction (default 0.95) for a
        candidate to be costed at all — below it, ``c_cost`` is inf.
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
        on_demand_price: float = 0.40,
        spot_price: float = 0.10,
        elastic: ElasticFleet = ElasticFleet(),
        slo_target: float = 0.95,
        capacities: Mapping[str, float] | None = None,
        sim: SimConfig = SimConfig(),
        chunk: int = 256,
        devices=None,
    ):
        if base.node_classes:
            raise ValueError(
                "CloudEvaluator's pOnDemandNodes/pSpotNodes axes define the "
                "fleet mix; pass a homogeneous base (no node_classes) and "
                "search the mix instead"
            )
        if on_demand_price < 0 or spot_price < 0:
            raise ValueError("hourly prices must be >= 0")
        if not 0.0 <= slo_target <= 1.0:
            raise ValueError("slo_target is a fraction in [0, 1]")
        self.on_demand_price = float(on_demand_price)
        self.spot_price = float(spot_price)
        self.slo_target = float(slo_target)
        self.elastic = elastic if elastic.extra_hourly_price is not None \
            else dataclasses.replace(
                elastic, extra_hourly_price=float(on_demand_price))
        super().__init__(
            classes, traces=traces, n_jobs=n_jobs, n_seeds=n_seeds,
            trace_seed=trace_seed, base=base, base_rate=base_rate,
            capacities=capacities, sim=sim, chunk=chunk, devices=devices,
            fleet={"pOnDemandNodes": base.num_nodes, "pSpotNodes": 0.0,
                   "spotReclaimRate": self.elastic.reclaim_rate,
                   "autoscalePolicy": self.elastic.policy_code,
                   "autoscaleHighWater": self.elastic.high_water,
                   "sloLatency": float("inf")})

    @property
    def cost_key(self) -> str:
        return "c_cost"

    @property
    def param_space(self) -> ParamSpace:
        """Declared cloud axes — the single source of the knob mask."""
        return cloud_space()

    def report(self, overrides) -> ProvisioningReport:
        """Typed evaluation: an overrides mapping (the ``api.sweep``
        convention) or an already-computed :class:`SearchResult`, lifted
        into the :class:`~repro.spec.ProvisioningReport` view."""
        result = overrides if isinstance(overrides, SearchResult) \
            else self.evaluate(overrides)
        return ProvisioningReport.from_outputs(result.outputs)

    def _fleet(self, col, mpn, rpn):
        od = np.maximum(np.round(col("pOnDemandNodes")), 0.0)
        sp = np.maximum(np.round(col("pSpotNodes")), 0.0)
        od = np.where(od + sp < 1.0, 1.0, od)
        rr = np.where(sp > 0, np.maximum(col("spotReclaimRate"), 0.0), 0.0)
        xpol = np.clip(np.round(col("autoscalePolicy")), 0.0,
                       float(len(AUTOSCALE_POLICIES) - 1))
        slo = col("sloLatency")
        el = self.elastic
        extra_on = np.where(xpol > 0.5, float(el.max_extra_nodes), 0.0)
        b = len(od)
        return od + sp, {
            # two class columns, spot first (both baseline speed — the
            # stable fastest-first sort keeps the declared order, and
            # autoscaled extra capacity joins the LAST = on-demand column)
            "map_slots": np.stack([sp * mpn, od * mpn], 1),
            "red_slots": np.stack([sp * rpn, od * rpn], 1),
            "speedup": np.stack([np.ones_like(sp), np.ones_like(od)], axis=1),
            "reclaim_rate": np.stack([rr, np.zeros_like(rr)], 1),
            "autoscale": xpol,
            "high_water": np.maximum(col("autoscaleHighWater"), 0.0),
            "provision_latency": np.full(b, float(el.provision_latency)),
            "extra_map_slots": extra_on * mpn,
            "extra_red_slots": extra_on * rpn,
            "billing_quantum": np.full(b, float(el.billing_quantum)),
        }, (sp, od, extra_on, np.where(slo > 0, slo, np.inf))

    def _resolve_config(self, cfg):
        od = int(round(cfg["pOnDemandNodes"]))
        sp = int(round(cfg["pSpotNodes"]))
        rr = float(cfg["spotReclaimRate"])
        xpol = int(round(cfg["autoscalePolicy"]))
        hw = float(cfg["autoscaleHighWater"])
        if (od < 0 or sp < 0 or od + sp < 1
                or rr < 0 or (rr > 0 and sp == 0)
                or not 0 <= xpol < len(AUTOSCALE_POLICIES)
                or hw < 0 or cfg["sloLatency"] <= 0):
            return None
        fleet = ()
        if sp > 0:                  # spot first — the wave class-column order
            fleet += (NodeClass(sp, 1.0, self.spot_price, spot=True),)
        if od > 0:
            fleet += (NodeClass(od, 1.0, self.on_demand_price, spot=False),)
        cc = self._cluster_config(cfg, od + sp, fleet)
        if cc is None:
            return None
        return cc, dataclasses.replace(
            self.elastic, policy=AUTOSCALE_POLICIES[xpol],
            high_water=hw, reclaim_rate=rr)

    def _reduce(self, out, fleet):
        """Prices the wave rollout: the base fleet billed over the
        workload span, autoscaled extras over their billed episodes."""
        sp, od, extra_on, slo = fleet
        b, s = self.chunk, len(self.traces)
        lat = out["latency"].reshape(b, s, -1)                  # (b, S, J)
        attain = np.where(
            np.isfinite(lat), lat <= slo[:, None, None], 0.0
        ).mean(axis=(1, 2))
        span = out["makespan"].reshape(b, s)                    # (b, S)
        billed = np.asarray(out.get(
            "extra_billed_s", np.zeros(b * s))).reshape(b, s)
        quantum = float(self.elastic.billing_quantum)
        if quantum > 0:
            span_b = np.ceil(span / quantum) * quantum
        else:
            span_b = span
        fleet_rate = sp * self.spot_price + od * self.on_demand_price
        extra_price = float(self.elastic.extra_hourly_price or 0.0)
        dollars = (fleet_rate[:, None] * span_b
                   + extra_price * extra_on[:, None] * billed) / 3600.0
        dpj = (dollars / lat.shape[-1]).mean(axis=1)
        feasible = attain >= self.slo_target - _SLO_EPS
        return {
            "c_dollarsPerJob": dpj.astype(np.float64),
            "c_dollarMakespan": dollars.mean(axis=1).astype(np.float64),
            "c_sloAttain": attain.astype(np.float64),
            "c_meanLat": self._per_row(out, "mean_latency"),
            "c_p95Lat": self._per_row(out, "p95_latency"),
            "c_util": self._per_row(out, "utilization"),
            # the objective: dollars-per-job where the SLO holds, inf where
            # it does not — an infeasible fleet is never "cheap"
            "c_cost": np.where(feasible, dpj, np.inf).astype(np.float64),
        }

    def _exact_value(self, cfg, cc, el, runs):
        """Bills the DES's recorded per-node online episodes, and raises
        :class:`SloUnmetError` when mean attainment misses ``slo_target``
        — never a silent inf."""
        dpj, attain = [], []
        for _, res in runs:
            # bill from the first submit (the wave span's origin) to the
            # last finish, so both backends price the same window
            first = min(j.submit_time for j in res.jobs)
            dollars = bill_workload(res, cc, elastic=el,
                                    window=(first, res.makespan))
            dpj.append(dollars / max(len(res.jobs), 1))
            attain.append(float((res.latencies() <= cfg["sloLatency"]).mean()))
        if float(np.mean(attain)) < self.slo_target - _SLO_EPS:
            raise SloUnmetError(
                f"SLO attainment {np.mean(attain):.3f} < target "
                f"{self.slo_target} at sloLatency={cfg['sloLatency']} — this "
                "fleet is not a feasible candidate"
            )
        return float(np.mean(dpj))
