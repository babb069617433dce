"""The analyzer's trace targets: every registered cost model + the
differentiated closures.

A :class:`TraceTarget` packages a callable-to-trace, the canonical example
arguments, and the per-input :class:`~repro.analysis.interval.Interval`
abstraction (from :mod:`repro.spec.axes` bounds where declared).  Checkers
consume the traced closed jaxpr — nothing here executes model math beyond
``jax.make_jaxpr`` tracing.

Targets:

* ``hadoop-model``   — the full branch-free job model (Eqs. 1-98) over the
  physical domain of :func:`repro.spec.hadoop_space`.
* ``hadoop-grad``    — the same jaxpr DCE'd to the ``j_totalCost`` output:
  exactly what :meth:`ChunkedEvaluator.grad_objective` differentiates.
* ``calib-loss``     — :func:`repro.calib.build_loss_fn` over canonical
  observations (the loss `jax.grad` descends in ``calibrate``).
* ``tuner-objective``— :func:`repro.search.strategies.build_relaxed_objective`
  for the Hadoop evaluator over a representative knob space.
* ``cluster-rollout``— the wave simulator ``_sim_one`` with every policy
  branch compiled in.
* ``cloud-rollout``  — the same rollout with the elastic-fleet path
  (``with_cloud``) compiled in: spot reclamation in expectation,
  autoscale on/off events, extra-capacity episode billing.
* ``cloud-pricing``  — the differentiable dollar path
  (``spot_inflation`` x ``dollars_for``) sensitivity studies descend;
  traced with a concrete zero billing quantum so it stays ceil-free.
* ``network-model``  — :func:`repro.cluster.network.effective_bandwidth`,
  the incast-contention factor the job model's topology hook divides
  Eq. 91's netCost by; differentiable in every topology knob.
* ``tpu-model``      — **not jaxpr-traceable** (a pure-numpy table model);
  registered with ``traceable=False`` so reports say *why* rather than
  silently skipping a registered model.  Its mask-contract obligations are
  checked at the AST level like every other evaluator.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Callable

from .interval import BOOL, FINITE_TOP, Interval

__all__ = ["TraceTarget", "iter_targets", "trace_target", "dce_to_outputs"]


@dataclass
class TraceTarget:
    name: str
    doc: str
    traceable: bool = True
    grad_mode: bool = False
    #: () -> (closed_jaxpr, [Interval]) — built lazily, tracing is not free
    build: Callable | None = None
    skip_reason: str = ""
    #: output names aligned with the jaxpr outputs (dict-output targets)
    out_names: tuple[str, ...] = field(default_factory=tuple)


def _axis_interval(ax) -> Interval:
    if ax.kind == "bool":
        return BOOL
    return Interval.bounded(ax.lower, ax.upper, getattr(ax, "lower_open", False))


def dce_to_outputs(closed, keep: list[int]):
    """Dead-code-eliminate a closed jaxpr down to the kept output indices —
    the analyzer's way of restricting to the differentiated path (e.g. the
    cost output of ``grad_objective``, not the validity flags)."""
    from jax import core as jcore
    from jax.interpreters import partial_eval as pe

    jaxpr = closed.jaxpr
    used = [i in keep for i in range(len(jaxpr.outvars))]
    new_jaxpr, used_inputs = pe.dce_jaxpr(jaxpr, used)
    consts = [c for c, u in zip(closed.consts, used_inputs[:len(closed.consts)])
              ] if len(new_jaxpr.constvars) != len(jaxpr.constvars) else \
        list(closed.consts)
    # pe.dce_jaxpr drops unused invars; constvars stay (closed jaxpr consts
    # are invars only after conversion) — rebuild a ClosedJaxpr
    return jcore.ClosedJaxpr(new_jaxpr, consts), used_inputs


# ---------------------------------------------------------------------------
# individual builders
# ---------------------------------------------------------------------------


def _hadoop_cfg_and_intervals():
    from repro.core.hadoop.model import pack_config
    from repro.core.hadoop.params import CostFactors, HadoopParams, ProfileStats
    from repro.spec import hadoop_space

    cfg = pack_config(HadoopParams(), ProfileStats(), CostFactors())
    space = hadoop_space()
    intervals = []
    for k in sorted(cfg):               # jax dict-pytree flatten order
        if k in space:
            intervals.append(_axis_interval(space[k]))
        else:
            intervals.append(Interval(0.0, math.inf, False, True))
    return cfg, intervals


def _build_hadoop_model():
    import jax

    from repro.core.hadoop.model import job_model_jnp

    cfg, intervals = _hadoop_cfg_and_intervals()
    names: list[str] = []

    def fn(c):
        out = job_model_jnp(c)
        names.extend(sorted(out))
        return {k: out[k] for k in sorted(out)}

    closed = jax.make_jaxpr(fn)(cfg)
    return closed, intervals, tuple(names)


def _build_hadoop_grad():
    import jax

    from repro.core.hadoop.model import job_model_jnp

    cfg, intervals = _hadoop_cfg_and_intervals()

    # exactly grad_objective's differentiated output: the raw total cost
    def fn(c):
        return job_model_jnp(c)["j_totalCost"]

    closed = jax.make_jaxpr(fn)(cfg)
    return closed, intervals, ("j_totalCost",)


def _canonical_observations():
    from repro.calib import Observation
    from repro.spec import JobSpec

    specs = [JobSpec(), JobSpec()]
    return [Observation(spec=s, cost=100.0 + 10.0 * i)
            for i, s in enumerate(specs)]


def _build_calib_loss():
    import jax
    import jax.numpy as jnp

    from repro.calib.fit import COST_FACTOR_NAMES, _stack_configs, build_loss_fn

    obs = _canonical_observations()
    cols = _stack_configs(obs)
    y = jnp.asarray([o.cost for o in obs], dtype=jnp.result_type(float))
    w = jnp.asarray([o.weight for o in obs], dtype=jnp.result_type(float))
    names = list(COST_FACTOR_NAMES)
    loss = build_loss_fn(cols, names, y, w)
    u0 = {n: jnp.asarray(0.0, dtype=jnp.result_type(float)) for n in names}
    closed = jax.make_jaxpr(loss)(u0)
    intervals = [FINITE_TOP for _ in names]   # unconstrained optimizer space
    return closed, intervals, ("loss",)


def _build_tuner_objective():
    import jax
    import jax.numpy as jnp

    from repro.core.hadoop.params import CostFactors, HadoopParams, ProfileStats
    from repro.search.evaluator import ChunkedEvaluator
    from repro.search.strategies import build_relaxed_objective

    ev = ChunkedEvaluator(HadoopParams(), ProfileStats(), CostFactors(),
                          chunk=16)
    space = {
        "pSortMB": [50.0, 100.0, 200.0],
        "pSortFactor": [5.0, 10.0, 50.0],
        "pSpillPerc": [0.5, 0.8, 0.95],
    }
    raw_cost, _axes, keys = build_relaxed_objective(ev, space)
    u0 = {k: jnp.asarray(0.0, dtype=jnp.result_type(float)) for k in keys}
    closed = jax.make_jaxpr(raw_cost)(u0)
    return closed, [FINITE_TOP for _ in keys], ("cost",)


def _build_cloud_pricing():
    import jax
    import jax.numpy as jnp

    from repro.cloud.pricing import dollars_for, spot_inflation

    C = 2
    fdt = jnp.result_type(float)
    a = {
        "counts": jnp.ones((C,), dtype=fdt),
        "prices": jnp.full((C,), 0.4, dtype=fdt),
        "rate": jnp.full((C,), 1e-4, dtype=fdt),
        "span": jnp.asarray(3600.0, dtype=fdt),
        "task_s": jnp.asarray(30.0, dtype=fdt),
    }
    ivals = {
        "counts": Interval(0.0, math.inf, False, True),
        "prices": Interval(0.0, math.inf, False, True),
        "rate": Interval(0.0, math.inf, False, True),
        "span": Interval(0.0, math.inf, False, True),
        "task_s": Interval(0.0, math.inf, True, True),
    }

    # the expected dollar cost of a spot fleet: the wall-clock span
    # inflates by the reclamation model, the fleet rate prices it.  A
    # concrete billing_quantum=0 keeps the path ceil-free — exactly the
    # differentiable surface spot_planning sensitivity studies use.
    def fn(arg):
        infl = spot_inflation(arg["rate"], arg["task_s"])
        # per-class: counts[c] * prices[c] * span * infl[c] / 3600
        per_class = dollars_for(
            arg["span"] * infl, arg["counts"] * jnp.eye(C), arg["prices"])
        return per_class.sum()

    closed = jax.make_jaxpr(fn)(a)
    intervals = [ivals[k] for k in sorted(a)]
    return closed, intervals, ("dollars",)


def _build_cluster_rollout():
    import jax
    import jax.numpy as jnp

    from repro.cluster.vector_sim import _sim_one

    J, C, Q = 3, 2, 2
    s = {
        "arrival": jnp.zeros((J,)),
        "n_maps": jnp.ones((J,)),
        "n_reds": jnp.ones((J,)),
        "map_cost": jnp.ones((J,)),
        "red_work": jnp.ones((J,)),
        "shuffle": jnp.ones((J,)),
        "queue": jnp.zeros((J,)),
        "map_slots": jnp.ones((C,)),
        "red_slots": jnp.ones((C,)),
        "speedup": jnp.ones((C,)),
        "policy": jnp.asarray(0.0, dtype=jnp.result_type(float)),
        "slowstart": jnp.asarray(0.05, dtype=jnp.result_type(float)),
        "queue_frac": jnp.full((Q,), 0.5, dtype=jnp.result_type(float)),
    }
    ivals = {
        "arrival": Interval(0.0, math.inf, False, True),
        "n_maps": Interval(0.0, math.inf, False, True),
        "n_reds": Interval(0.0, math.inf, False, True),
        "map_cost": Interval(0.0, math.inf, False, True),
        "red_work": Interval(0.0, math.inf, False, True),
        "shuffle": Interval(0.0, math.inf, False, True),
        "queue": Interval(0.0, float(Q - 1)),
        "map_slots": Interval(0.0, math.inf, False, True),
        "red_slots": Interval(0.0, math.inf, False, True),
        "speedup": Interval(1.0, math.inf, False, True),
        "policy": Interval(0.0, 3.0),
        "slowstart": Interval(0.0, 1.0),
        "queue_frac": Interval(0.0, 1.0),
    }
    names: list[str] = []

    def fn(scen):
        out = _sim_one(scen, None, 8, True, True, True)
        names.extend(sorted(out))
        return {k: out[k] for k in sorted(out)}

    closed = jax.make_jaxpr(fn)(s)
    intervals = [ivals[k] for k in sorted(s)]
    return closed, intervals, tuple(names)


def _build_cloud_rollout():
    import jax
    import jax.numpy as jnp

    from repro.cluster.vector_sim import _sim_one

    J, C, Q = 3, 2, 2
    fdt = jnp.result_type(float)
    s = {
        "arrival": jnp.zeros((J,)),
        "n_maps": jnp.ones((J,)),
        "n_reds": jnp.ones((J,)),
        "map_cost": jnp.ones((J,)),
        "red_work": jnp.ones((J,)),
        "shuffle": jnp.ones((J,)),
        "queue": jnp.zeros((J,)),
        "map_slots": jnp.ones((C,)),
        "red_slots": jnp.ones((C,)),
        "speedup": jnp.ones((C,)),
        "policy": jnp.asarray(0.0, dtype=fdt),
        "slowstart": jnp.asarray(0.05, dtype=fdt),
        "queue_frac": jnp.full((Q,), 0.5, dtype=fdt),
        "reclaim_rate": jnp.full((C,), 1e-4, dtype=fdt),
        "autoscale": jnp.asarray(1.0, dtype=fdt),
        "high_water": jnp.asarray(2.0, dtype=fdt),
        "provision_latency": jnp.asarray(5.0, dtype=fdt),
        "extra_map_slots": jnp.asarray(2.0, dtype=fdt),
        "extra_red_slots": jnp.asarray(2.0, dtype=fdt),
        "billing_quantum": jnp.asarray(60.0, dtype=fdt),
    }
    nonneg = Interval(0.0, math.inf, False, True)
    ivals = {
        "arrival": nonneg,
        "n_maps": nonneg,
        "n_reds": nonneg,
        "map_cost": nonneg,
        "red_work": nonneg,
        "shuffle": nonneg,
        "queue": Interval(0.0, float(Q - 1)),
        "map_slots": nonneg,
        "red_slots": nonneg,
        "speedup": Interval(1.0, math.inf, False, True),
        "policy": Interval(0.0, 3.0),
        "slowstart": Interval(0.0, 1.0),
        "queue_frac": Interval(0.0, 1.0),
        "reclaim_rate": nonneg,
        "autoscale": Interval(0.0, 2.0),
        "high_water": nonneg,
        "provision_latency": nonneg,
        "extra_map_slots": nonneg,
        "extra_red_slots": nonneg,
        "billing_quantum": nonneg,
    }
    names: list[str] = []

    def fn(scen):
        out = _sim_one(scen, None, 8, True, True, True, True)
        names.extend(sorted(out))
        return {k: out[k] for k in sorted(out)}

    closed = jax.make_jaxpr(fn)(s)
    intervals = [ivals[k] for k in sorted(s)]
    return closed, intervals, tuple(names)


def _build_network_model():
    import jax
    import jax.numpy as jnp

    from repro.cluster.network import effective_bandwidth

    fdt = jnp.result_type(float)
    a = {
        "pNumRacks": jnp.asarray(4.0, dtype=fdt),
        "crossRackBw": jnp.asarray(2.0, dtype=fdt),
        "oversubscription": jnp.asarray(2.0, dtype=fdt),
        "nFlows": jnp.asarray(8.0, dtype=fdt),
    }
    ivals = {
        "pNumRacks": Interval(1.0, math.inf, False, True),
        "crossRackBw": Interval(0.0, math.inf, True, True),
        "oversubscription": Interval(1.0, math.inf, False, True),
        "nFlows": Interval(0.0, math.inf, False, True),
    }

    # the effective shuffle bandwidth dividing Eq. 91's netCost in the
    # closed-form topology hook — the surface pNumRacks / crossRackBw /
    # oversubscription gradients flow through
    def fn(arg):
        return effective_bandwidth(
            arg["pNumRacks"], arg["crossRackBw"],
            arg["oversubscription"], arg["nFlows"])

    closed = jax.make_jaxpr(fn)(a)
    intervals = [ivals[k] for k in sorted(a)]
    return closed, intervals, ("bandwidth",)


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------


def iter_targets() -> list[TraceTarget]:
    """All analyzer targets, untraced (call :func:`trace_target` per item)."""
    return [
        TraceTarget(
            name="hadoop-model",
            doc="full job model (Eqs. 1-98) over the physical axis domain",
            build=_build_hadoop_model,
        ),
        TraceTarget(
            name="hadoop-grad",
            doc="the j_totalCost path grad_objective differentiates",
            build=_build_hadoop_grad,
            grad_mode=True,
        ),
        TraceTarget(
            name="calib-loss",
            doc="repro.calib.build_loss_fn over canonical observations",
            build=_build_calib_loss,
            grad_mode=True,
        ),
        TraceTarget(
            name="tuner-objective",
            doc="build_relaxed_objective raw cost (gradient_descent_ev)",
            build=_build_tuner_objective,
            grad_mode=True,
        ),
        TraceTarget(
            name="cluster-rollout",
            doc="vector_sim._sim_one wave rollout, all policies compiled in",
            build=_build_cluster_rollout,
        ),
        TraceTarget(
            name="cloud-rollout",
            doc="the wave rollout with the elastic-fleet path compiled in "
                "(spot reclamation, autoscaling, episode billing)",
            build=_build_cloud_rollout,
        ),
        TraceTarget(
            name="cloud-pricing",
            doc="the differentiable spot-pricing path (spot_inflation x "
                "dollars_for), quantum-free so grad stays clean",
            build=_build_cloud_pricing,
            grad_mode=True,
        ),
        TraceTarget(
            name="network-model",
            doc="the topology-aware effective shuffle bandwidth dividing "
                "Eq. 91's netCost (incast contention, differentiable)",
            build=_build_network_model,
            grad_mode=True,
        ),
        TraceTarget(
            name="tpu-model",
            doc="TPU step table model (registered CostModel 'tpu')",
            traceable=False,
            skip_reason=(
                "pure-numpy table model over integer mesh layouts — no jaxpr "
                "exists; covered by the AST-level mask-contract checker and "
                "its own shardability predicates"),
        ),
    ]


def trace_target(t: TraceTarget):
    """Build (closed_jaxpr, intervals, out_names) for a traceable target."""
    if not t.traceable:
        raise ValueError(f"target {t.name} is not traceable: {t.skip_reason}")
    closed, intervals, names = t.build()
    t.out_names = names
    return closed, intervals, names
