"""Vectorized wave-level cluster simulator (thousands of scenarios per call).

The Python DES (:mod:`repro.cluster.sched`) costs ONE (workload, cluster)
scenario per call — fine for a probe, hopeless for a capacity-planning grid.
This module rolls out the same wave mechanics as a JAX program: one
``lax.scan`` over *scheduling rounds* (global event times), ``vmap`` over
scenarios, device-sharded over the scenario axis via the :mod:`repro.compat`
shims — one compile per (step-count bucket, batch shape), exactly the
:class:`~repro.search.evaluator.ChunkedEvaluator` recipe.

Model (wave-discrete, deterministic):

* a job's launched tasks form *wave buckets* that complete together after
  one task duration — launches at an event join (and extend) the bucket;
* **heterogeneous fleets**: slots live in per-class columns (``C`` node
  classes, fastest first); a task launched into class ``c`` runs its
  compute ``speedup[c]`` times faster (the shuffle is network-bound and
  unscaled), so each job carries one wave bucket *per class* and free
  slots fill fast classes first — the DES's free-slot order;
* FIFO hands free slots to jobs in arrival order (prefix-sum allocation);
  fair-share water-fills the pool (integer max-min shares);
* **preemptive policies** reallocate at wave boundaries: at every event
  the scheduler recomputes each job's *target* allocation over the total
  capacity — fair water-fill (``fair_preempt``) or per-queue guaranteed
  capacities with FIFO spill (``capacity``) — kills running slots above
  the target (requeued to the todo pool, slowest class first; killed work
  is lost, as in the DES's kill-and-requeue) and launches up to it.  The
  DES's ``preempt_timeout`` grace is below wave resolution: the wave
  model preempts immediately at event boundaries, which the agreement
  tolerance for preemptive scenarios absorbs;
* reduces honor slowstart and the two-phase semantics: waves launched
  before the job's maps finish stall, then complete at
  ``max(map_finish, start + shuffle) + work`` — the DES rule verbatim.

Fidelity: on **contention-free FIFO** scenarios (every job's wave gets its
full slot demand the moment it asks — serialized jobs, or an unsaturated
cluster) wave buckets coincide with the DES's task waves and the rollout
reproduces per-job finish times *exactly* (float32 rounding aside; the
agreement test asserts rtol 1e-3) — including heterogeneous fleets, where
both models fill the fast class first and each class's sub-wave completes
at its own scaled duration.  Under slot contention partial waves merge
into one bucket per (job, class), a work-conserving approximation the
capacity planner accepts in exchange for ~3 orders of magnitude more
scenarios/s; ``ClusterEvaluator.exact_cost`` routes final candidates back
through the DES.

Scenario batches are dicts of arrays (B = scenarios, J = jobs, C = node
classes, Q = capacity queues):

  arrival (B, J)    n_maps (B, J)     n_reds (B, J)     map_cost (B, J)
  red_work (B, J)   shuffle (B, J)    queue (B, J)
  map_slots (B, C)  red_slots (B, C)  speedup (B, C)
  policy (B,)       slowstart (B,)    queue_frac (B, Q)

**DAG workloads** add ``dep`` / ``dep_kind`` (B, J, P) edge columns
(default -1 / 0): ``dep[j, p]`` is
the index of job ``j``'s ``p``-th parent, -1 for none, and job ``j`` is
held until the latest of its parents' milestones — a parent's finish
(kind 0, barrier) or its map-phase finish (kind 1, slowstart) — and
arrives one zero-advance step after the milestone that frees it.  One
path serves chains, trees and fan-in joins alike.  **Topology-aware shuffle**
adds ``topo_racks`` / ``topo_cross_bw`` / ``topo_oversub`` (B,) columns
(default 1 / inf / 1): each reduce wave's shuffle term is divided by the
rack-incast effective bandwidth
(:func:`repro.cluster.network.effective_bandwidth`) at its launch-time
concurrent-transfer count.  The bucket keeps its launch-time bandwidth —
the DES re-fair-shares continuously and is the exact reference, so
contended-incast agreement is gated at p95 (flat/uncontended rows stay
rtol-exact, the standard contract).

``policy`` is 0 = fifo, 1 = fair, 2 = fair_preempt, 3 = capacity (the
:data:`POLICIES` order).  :func:`simulate_batch` normalizes legacy inputs:
a ``fair`` (B,) column is accepted as ``policy``, 1-D ``map_slots`` /
``red_slots`` become one baseline class, ``speedup`` defaults to ones
(classes are re-sorted fastest-first), ``queue`` / ``queue_frac`` default
to a single queue.

**Elastic fleets** (:mod:`repro.cloud`) add optional columns, all
defaulting to the fixed-fleet zero:

  autoscale (B,)          0 = off, 1 = queue-depth, 2 = predicted-load
  high_water (B,)         unmet-task trigger threshold (queue policy)
  provision_latency (B,)  request -> schedulable seconds
  extra_map_slots (B,)    autoscaled capacity block (joins the LAST class)
  extra_red_slots (B,)
  billing_quantum (B,)    minimum billed seconds per capacity episode
  reclaim_rate (B, C)     spot reclaims per node-second, per class

Fleet size becomes a per-round dynamic column: the extra block turns on
one provisioning latency after its trigger and turns off at the first
event where nothing is queued and the block is idle; its billed seconds
(episodes rounded up to the billing quantum) come back as
``extra_billed_s``.  Spot reclamation enters in expectation: a class with
reclaim rate λ runs its task of length d in ``(e^{λd} - 1)/λ`` expected
seconds (restart-from-scratch under a Poisson reclaim process) — the DES
realizes actual reclaim draws and is the exact reference, so agreement on
reclaiming workloads is gated at the p95 level, not per-job (the PR 5
contract: contention-free autoscaled cases stay rtol-exact).

Use :func:`pack_trace` to turn a :class:`~repro.cluster.workload.
WorkloadTrace` into per-job columns, and :func:`estimate_steps` to bound
the scan length (truncated scenarios report ``converged == 0``, which the
evaluator maps to ``valid == 0`` — the exact-simulator escape hatch, never
a silent wrong number).
"""

from __future__ import annotations

import functools
from typing import Mapping

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import compat
from repro.obs import current as _obs_current

from .network import effective_bandwidth
from .workload import WorkloadTrace, shuffle_full, task_costs

__all__ = ["POLICIES", "latency_quantile", "pack_trace", "pack_traces",
           "estimate_steps", "simulate_batch"]

_EPS = 1e-3          # event-time / task-count slack (durations are >= ~0.1 s)
_INF = jnp.inf

#: scheduler-policy encoding of the ``policy`` scenario column — index into
#: this tuple; matches ``ClusterConfig.scheduler`` names.
POLICIES = ("fifo", "fair", "fair_preempt", "capacity")


def pack_trace(trace: WorkloadTrace) -> dict[str, np.ndarray]:
    """Per-job columns (J,) for one trace, and its DAG edges as (J, P)
    columns.  ``shuffle`` is the all-remote limit
    (:func:`~repro.cluster.workload.shuffle_full`); multiply by the
    candidate cluster's remote fraction ``(n-1)/n`` before simulating.
    ``queue`` is the job's capacity-scheduler queue: the index of its job
    class name in sorted order (the DES's queue enumeration)."""
    cols = {k: [] for k in ("arrival", "n_maps", "n_reds", "map_cost",
                            "red_work", "shuffle", "queue")}
    qidx = {name: i for i, name in
            enumerate(sorted({a.klass.name for a in trace.arrivals}))}
    pos = {a.job_id: i for i, a in enumerate(trace.arrivals)}
    for a in trace.arrivals:
        mc, rc, _ = task_costs(a.klass)
        cols["arrival"].append(a.submit_time)
        cols["n_maps"].append(a.klass.n_maps)
        cols["n_reds"].append(a.klass.n_reduces)
        cols["map_cost"].append(mc)
        cols["red_work"].append(rc)
        cols["shuffle"].append(shuffle_full(a.klass))
        cols["queue"].append(qidx[a.klass.name])
    out = {k: np.asarray(v, dtype=np.float64) for k, v in cols.items()}
    # DAG edges: P is the trace's most parents of one job (1 for a chain or
    # no DAG), unused slots -1; kind 0 = barrier, 1 = slowstart.  Indices,
    # so integer columns: they ride every scenario of the trace unchanged.
    n_par = max((len(a.deps) for a in trace.arrivals), default=0)
    dep = np.full((trace.n_jobs, max(n_par, 1)), -1, dtype=np.int32)
    kind = np.zeros(dep.shape, dtype=np.int8)
    for j, a in enumerate(trace.arrivals):
        for p, (parent, edge) in enumerate(a.deps):
            dep[j, p] = pos[parent]
            kind[j, p] = edge == "slowstart"
    out["dep"], out["dep_kind"] = dep, kind
    return out


def pack_traces(traces) -> dict[str, np.ndarray]:
    """:func:`pack_trace` of each trace, stacked: (S, J) job columns and
    (S, J, P) edge columns, P the largest of the traces' (every trace
    needs the same job count)."""
    packed = [pack_trace(t) for t in traces]
    n_par = max(p["dep"].shape[1] for p in packed)
    for p in packed:
        pad = ((0, 0), (0, n_par - p["dep"].shape[1]))
        p["dep"] = np.pad(p["dep"], pad, constant_values=-1)
        p["dep_kind"] = np.pad(p["dep_kind"], pad)
    return {k: np.stack([p[k] for p in packed]) for k in packed[0]}


def estimate_steps(scen: Mapping[str, np.ndarray], *, margin: float = 2.0
                   ) -> int:
    """Step *cap* covering every wave event, rounded up to a power of two
    so compile count stays bounded across workloads.  The rollout is a
    ``while_loop`` that stops at the batch's last event, so a generous cap
    costs nothing, and truncation at the cap is detected, not silent
    (``converged``).

    Without preemption the cap is a bound: every step retires a wave
    bucket of at least one whole task, admits a job or releases one, so a
    row's tasks plus twice its jobs bound its steps.  Fair sharing among
    many concurrent jobs needs that room: each job's waves run on its
    slice of the slots, not the whole pool.  Preemptive rows re-run killed
    tasks, so theirs is an estimate: the waves on the whole pool times
    ``margin``, doubled (kills re-fragment waves)."""
    def total(key):
        a = np.asarray(scen[key], dtype=np.float64)
        return np.maximum(a.sum(axis=-1) if a.ndim == 2 else a, 1.0)
    n_maps, n_reds = np.asarray(scen["n_maps"]), np.asarray(scen["n_reds"])
    ms, rs = total("map_slots"), total("red_slots")
    waves = (np.ceil(n_maps / ms[:, None]).sum(axis=1)
             + np.ceil(n_reds / rs[:, None]).sum(axis=1))
    pol = np.broadcast_to(np.asarray(scen.get("policy", scen.get("fair", 0.0))),
                          waves.shape)
    preempt = pol > 1.5
    n_jobs = scen["arrival"].shape[-1]
    est = int(np.max(waves, where=preempt, initial=0) * margin * 2.0)
    est = max(est, int(np.max((n_maps + n_reds).sum(axis=1), where=~preempt,
                              initial=0)))
    # one event per arrival and one zero-advance step per DAG release
    est += 2 * n_jobs + 8
    if (np.any(np.asarray(scen.get("autoscale", 0.0)) > 0.5)
            or np.any(np.asarray(scen.get("extra_map_slots", 0.0)) > 0)):
        # elastic rows add provision/teardown events (the queue policy can
        # cycle once per burst)
        est += n_jobs + 8
    return 1 << (est - 1).bit_length()


# --------------------------------------------------------------------------
# allocation primitives (single scenario; all shapes noted for one row)
# --------------------------------------------------------------------------


def _prefix(demand, cap):
    """FIFO: prefix allocation in arrival order (jobs are arrival-sorted)."""
    cum = jnp.cumsum(demand) - demand
    return jnp.clip(cap - cum, 0.0, demand)


def _waterfill(demand, cap):
    """Fair: integer equal shares, leftover spilled FIFO (a one-pass
    max-min approximation; the DES is the slot-exact reference).  Whole
    slots throughout, matching the DES's slot granularity — fractional
    shares would extend wave buckets by a full task duration for an
    epsilon of work and never converge."""
    act = demand > _EPS
    share = jnp.floor(cap / jnp.maximum(act.sum(), 1) + _EPS)
    a = jnp.minimum(demand, share)
    need = demand - a
    cum2 = jnp.cumsum(need) - need
    return a + jnp.clip(jnp.floor(cap - a.sum() + _EPS) - cum2, 0.0, need)


def _capacity_fill(demand, cap, onehot, queue_frac):
    """Capacity scheduler target: pass 1 fills each queue up to its
    guaranteed slot count (``floor(frac * cap)``, FIFO within the queue);
    pass 2 spills the leftover capacity FIFO over the remaining demand."""
    # sum(floor(frac * cap)) <= cap because fracs sum to <= 1 (normalized
    # by _normalize), so pass 1 never over-allocates the pool
    qcap = jnp.floor(queue_frac * cap + _EPS)                 # (Q,)
    d_q = demand[:, None] * onehot                            # (J, Q)
    prev_q = ((jnp.cumsum(d_q, axis=0) - d_q) * onehot).sum(-1)
    budget = (onehot * qcap[None, :]).sum(-1)                 # (J,)
    a1 = jnp.clip(budget - prev_q, 0.0, demand)
    return a1 + _prefix(demand - a1, cap - a1.sum())


def _by_class(alloc, free_c):
    """Distribute per-job allocations over per-class free slots, fastest
    class first: job j's slots occupy the interval
    ``[cumsum(alloc)_{j-1}, cumsum(alloc)_j)`` of the concatenated
    class-ordered slot space — the order the DES's free-slot picker
    produces when it launches tasks one at a time."""
    if free_c.shape[0] == 1:       # homogeneous: keep the lean kernel
        return alloc[:, None]
    off_hi = jnp.cumsum(free_c)
    off_lo = off_hi - free_c
    start = (jnp.cumsum(alloc) - alloc)[:, None]
    stop = start + alloc[:, None]
    return jnp.clip(jnp.minimum(stop, off_hi[None, :])
                    - jnp.maximum(start, off_lo[None, :]), 0.0, None)


def _take_rev(amount, buckets):
    """Take ``amount[j]`` slots out of ``buckets[j, :]`` starting from the
    LAST class (slowest) — preemption victims lose slow slots first, the
    class-ordered analogue of the DES killing the newest launch."""
    rev = buckets[:, ::-1]
    cum = jnp.cumsum(rev, axis=1) - rev
    take = jnp.clip(amount[:, None] - cum, 0.0, rev)
    return take[:, ::-1]


def _quantize(dur, quantum):
    """Round a billing episode up to the minimum billing granularity
    (0 = per-second billing).  Double-where so quantum 0 never divides."""
    q_safe = jnp.where(quantum > 0, quantum, 1.0)
    return jnp.where(quantum > 0, jnp.ceil(dur / q_safe) * q_safe, dur)


def latency_quantile(values, q: float):
    """Linear-interpolated quantile of a 1-D array — the JAX twin of
    :func:`repro.obs.percentile_interp`, the repo's one percentile rule,
    with the same small-sample semantics: empty -> 0, one sample -> that
    sample for every ``q``, integral ranks return the order statistic
    exactly, and equal neighbours (both inf included) return the common
    value.  ``WorkloadResult.latency_quantile`` is the DES-side twin."""
    v = jnp.sort(jnp.ravel(jnp.asarray(values)))
    n = v.shape[0]
    if n == 0:
        return jnp.zeros((), dtype=jnp.result_type(float))
    if n == 1:
        return v[0]
    rank = jnp.clip(jnp.asarray(q, dtype=v.dtype), 0.0, 100.0) \
        / 100.0 * (n - 1)
    lo = jnp.floor(rank).astype(jnp.int32)
    hi = jnp.minimum(lo + 1, n - 1)
    frac = rank - lo.astype(v.dtype)
    a, b = v[lo], v[hi]
    # double-where (the PR 7 inf guard): when the neighbours agree or the
    # rank is integral the answer is ``a`` — never compute ``b - a`` there,
    # because with infinite neighbours that difference is inf - inf = nan
    same = (frac <= 0.0) | (a == b)
    delta = jnp.where(same, 0.0, b - a)
    return jnp.where(same, a, a + delta * frac)


# --------------------------------------------------------------------------
# core rollout (single scenario; vmapped + sharded below)
# --------------------------------------------------------------------------


def _sim_one(s: dict, edges, n_steps: int, with_fair: bool, with_preempt: bool,
             with_capacity: bool, with_cloud: bool = False,
             with_dag: bool = False, with_topo: bool = False) -> dict:
    arrival = s["arrival"]
    n_maps = s["n_maps"]
    n_reds = s["n_reds"]
    map_cost = jnp.maximum(s["map_cost"], 1e-9)
    map_slots = s["map_slots"]          # (C,) per-class, fastest first
    red_slots = s["red_slots"]
    speedup = jnp.maximum(s["speedup"], 1e-9)
    policy = s["policy"]
    slowstart = s["slowstart"]
    J = arrival.shape[0]
    C = map_slots.shape[0]
    cap_m = map_slots.sum()
    cap_r = red_slots.sum()
    # per-class task durations: compute scales with the class, network not
    map_dur = map_cost[:, None] / speedup[None, :]            # (J, C)
    red_dur = s["shuffle"][:, None] + s["red_work"][:, None] / speedup[None, :]
    if with_cloud:
        # spot reclamation in expectation: restart-from-scratch under a
        # Poisson(λ) reclaim process makes a length-d task take
        # (e^{λd} - 1)/λ expected seconds (-> d as λ -> 0); stalled-reduce
        # resolution keeps the uninflated work term — reclaim rates sane
        # enough to converge make that correction second-order.  Double-
        # where so λ = 0 classes never divide by zero.
        rate = jnp.maximum(s["reclaim_rate"], 0.0)            # (C,)
        rate_safe = jnp.where(rate > 0, rate, 1.0)

        def inflate(d):
            return jnp.where(
                rate[None, :] > 0,
                jnp.expm1(rate_safe[None, :] * d) / rate_safe[None, :], d)

        map_dur = inflate(map_dur)
        red_dur = inflate(red_dur)
        x_policy = s["autoscale"]
        high_water = s["high_water"]
        x_lat = s["provision_latency"]
        x_m = s["extra_map_slots"]
        x_r = s["extra_red_slots"]
        x_quant = s["billing_quantum"]
        have_extra = (x_m + x_r) > _EPS
        # the autoscaled block joins the LAST class column: extra capacity
        # clones the baseline (slowest) class, the DES's rule
        onehot_last = (jnp.arange(C) == C - 1).astype(arrival.dtype)
    if with_capacity:
        qf = s["queue_frac"]
        onehot = (jnp.round(s["queue"])[:, None]
                  == jnp.arange(qf.shape[0])[None, :]).astype(arrival.dtype)
    if with_dag:
        # DAG edges: the batch's distinct (J, P) edge tables, shared by its
        # lanes (``edges``, sources into [finish, map-finish] by position,
        # -1 none), and this lane's table.  A job is freed once every
        # parent's milestone has landed, counted as a product of the
        # table's 0/1 adjacency with the lane's milestones-landed vector:
        # one matmul over the lanes a step, where a per-lane gather of
        # parents runs serially on the TPU.  It arrives at the latest
        # parent's milestone, the time of the step that frees it.
        n_tab = edges.shape[0]
        # 0/1 entries and counts of a few parents are exact in any float
        # matmul; int8 runs slower on the chip
        adj = jax.nn.one_hot(edges, 2 * J, dtype=jnp.float32).sum(2)     # (U, J, 2J)
        mine = jnp.arange(n_tab) == s["edge_id"]                         # (U,)
        n_par = jnp.where(mine[:, None], (edges >= 0).sum(-1), 0).sum(0)  # (J,)

        def freed_by(map_fin_col, fin_col):
            landed = jnp.concatenate([jnp.isfinite(fin_col),
                                      jnp.isfinite(map_fin_col)]).astype(jnp.float32)
            cnt = jnp.einsum("ujk,k->uj", adj, landed,
                             preferred_element_type=jnp.float32)
            return jnp.where(mine[:, None], cnt, 0.0).sum(0) >= n_par

        def release_at(release, map_fin_col, fin_col, t):
            return jnp.where((release == _INF) & freed_by(map_fin_col, fin_col),
                             t, release)
    if with_topo:
        def shuffle_eff(n_flows):
            # per-rack incast contention: concurrent transfers share the
            # aggregation downlinks; bw floor keeps the division benign on
            # degenerate zero-capacity rows (evaluators sanitize earlier)
            bw = effective_bandwidth(s["topo_racks"], s["topo_cross_bw"],
                                     s["topo_oversub"], n_flows)
            return s["shuffle"] / jnp.maximum(bw, 1e-9)

    def alloc_free(demand, free_c):
        """Non-preemptive policies: hand the free slots to demand."""
        a = _prefix(demand, free_c.sum())
        if with_fair:
            a = jnp.where(policy > 0.5, _waterfill(demand, free_c.sum()), a)
        return a

    def target_alloc(demand_tot, cap):
        """Preemptive policies: the ideal allocation over TOTAL capacity."""
        tgt = _waterfill(demand_tot, cap)
        if with_capacity:
            tgt = jnp.where(policy > 2.5,
                            _capacity_fill(demand_tot, cap, onehot, qf), tgt)
        return tgt

    state0 = dict(
        k=jnp.asarray(0),
        t=arrival.min(),
        m_todo=n_maps * 1.0, m_run=jnp.zeros((J, C), arrival.dtype),
        m_end=jnp.full((J, C), _INF, arrival.dtype),
        r_todo=n_reds * 1.0, r_run=jnp.zeros((J, C), arrival.dtype),
        r_end=jnp.full((J, C), _INF, arrival.dtype),
        r_pre=jnp.zeros((J, C), arrival.dtype),
        r_pre_start=jnp.full((J, C), _INF, arrival.dtype),
        red_launch=jnp.full_like(arrival, _INF),
        map_fin=jnp.full_like(arrival, _INF),
        fin=jnp.full_like(arrival, _INF),
    )
    if with_cloud:
        # predicted-load provisions up front: extra capacity is requested
        # the moment the workload starts (x_at = first arrival + latency);
        # the queue policy arms x_at when the trigger fires mid-run
        state0.update(
            x_on=jnp.zeros((), arrival.dtype),
            x_at=jnp.where((x_policy > 1.5) & have_extra,
                           arrival.min() + x_lat,
                           jnp.asarray(_INF, arrival.dtype)),
            x_t_on=jnp.asarray(_INF, arrival.dtype),
            x_billed=jnp.zeros((), arrival.dtype),
        )
    if with_dag:
        # release time per job (-inf: no parents), and release steps
        state0.update(release=jnp.where(n_par > 0, _INF, -_INF).astype(arrival.dtype),
                      rel=jnp.asarray(0, jnp.int32))

    def step(st):
        t = st["t"]
        if with_dag:
            # releases land on the previous state's milestones, so a child
            # released at this instant arrives one (zero-advance) step later
            eligible = jnp.maximum(arrival, st["release"])
        else:
            eligible = arrival
        arrived = eligible <= t + _EPS

        if with_cloud:
            # pending provisioning lands: the block comes online for this
            # round's allocation, one episode (x_t_on) starts billing
            turn_on = (st["x_at"] <= t + _EPS) & (st["x_on"] < 0.5)
            x_on = jnp.where(turn_on, 1.0, st["x_on"])
            x_at = jnp.where(turn_on, _INF, st["x_at"])
            x_t_on = jnp.where(turn_on, t, st["x_t_on"])
            x_billed = st["x_billed"]
            map_slots_t = map_slots + x_on * x_m * onehot_last
            red_slots_t = red_slots + x_on * x_r * onehot_last
            cap_m_t = cap_m + x_on * x_m
            cap_r_t = cap_r + x_on * x_r
        else:
            map_slots_t, red_slots_t = map_slots, red_slots
            cap_m_t, cap_r_t = cap_m, cap_r

        # (a) wave buckets due now complete (per job x class)
        m_done_now = (st["m_run"] > _EPS) & (st["m_end"] <= t + _EPS)
        m_run = jnp.where(m_done_now, 0.0, st["m_run"])
        m_end = jnp.where(m_done_now, _INF, st["m_end"])
        r_done_now = (st["r_run"] > _EPS) & (st["r_end"] <= t + _EPS)
        r_run = jnp.where(r_done_now, 0.0, st["r_run"])
        r_end = jnp.where(r_done_now, _INF, st["r_end"])
        m_todo, r_todo = st["m_todo"], st["r_todo"]
        r_pre, r_pre_start = st["r_pre"], st["r_pre_start"]

        # (b) milestones: map fleet done, slowstart crossed, job finished
        maps_done = arrived & (m_todo <= _EPS) & (m_run.sum(-1) <= _EPS)
        just_mf = jnp.isinf(st["map_fin"]) & maps_done
        map_fin = jnp.where(just_mf, t, st["map_fin"])

        done_cnt = n_maps - m_todo - m_run.sum(-1)
        slow_ok = arrived & (done_cnt >= slowstart * n_maps - _EPS)
        red_launch = jnp.where(jnp.isinf(st["red_launch"]) & slow_ok, t,
                               st["red_launch"])

        # stalled pre-map-finish reduce wave resolves (the DES rule)
        resolve = just_mf[:, None] & (r_pre > _EPS)
        if with_topo:
            # contention at resolve time: running + stalled transfers share
            # the racks (the DES recomputes continuously; this snapshot is
            # the wave approximation the agreement gate bounds at p95)
            shuf_res = shuffle_eff((r_run + r_pre).sum())
        else:
            shuf_res = s["shuffle"]
        e1 = (jnp.maximum(map_fin[:, None], r_pre_start + shuf_res[:, None])
              + s["red_work"][:, None] / speedup[None, :])
        r_end = jnp.where(
            resolve,
            jnp.maximum(jnp.where(r_run > _EPS, r_end, -_INF), e1), r_end)
        r_run = jnp.where(resolve, r_run + r_pre, r_run)
        r_pre = jnp.where(resolve, 0.0, r_pre)
        r_pre_start = jnp.where(resolve, _INF, r_pre_start)

        reds_done = ((r_todo <= _EPS) & (r_run.sum(-1) <= _EPS)
                     & (r_pre.sum(-1) <= _EPS))
        finished = arrived & maps_done & jnp.where(n_reds > 0, reds_done, True)
        fin = jnp.where(jnp.isinf(st["fin"]) & finished, t, st["fin"])

        # (c) map slots
        m_demand = jnp.where(arrived & (m_todo > _EPS), m_todo, 0.0)
        if with_preempt:
            preempt = policy > 1.5
            target = target_alloc(m_demand + m_run.sum(-1), cap_m_t)
            kill = jnp.where(preempt,
                             jnp.clip(m_run.sum(-1) - target, 0.0, None), 0.0)
            kill_c = _take_rev(kill, m_run)
            m_run = m_run - kill_c
            m_todo = m_todo + kill_c.sum(-1)     # killed work re-runs fully
            m_end = jnp.where(m_run > _EPS, m_end, _INF)
            m_demand = jnp.where(arrived & (m_todo > _EPS), m_todo, 0.0)
            free_m = map_slots_t - m_run.sum(0)
            alloc = jnp.where(
                preempt,
                jnp.clip(target - m_run.sum(-1), 0.0, m_demand),
                alloc_free(m_demand, free_m))
        else:
            free_m = map_slots_t - m_run.sum(0)
            alloc = alloc_free(m_demand, free_m)
        k_m = _by_class(alloc, free_m)
        launched = k_m > _EPS
        m_end = jnp.where(
            launched,
            jnp.maximum(jnp.where(m_run > _EPS, m_end, -_INF), t + map_dur),
            m_end)
        m_run = m_run + k_m
        m_todo = m_todo - k_m.sum(-1)

        # (d) reduce slots (gated on slowstart; pre-map-finish waves stall)
        r_demand = jnp.where((red_launch <= t + _EPS) & (r_todo > _EPS),
                             r_todo, 0.0)
        if with_preempt:
            run_tot = r_run.sum(-1) + r_pre.sum(-1)
            target = target_alloc(r_demand + run_tot, cap_r_t)
            kill = jnp.where(preempt, jnp.clip(run_tot - target, 0.0, None),
                             0.0)
            take_pre = _take_rev(kill, r_pre)      # stalled buckets first
            r_pre = r_pre - take_pre
            take_run = _take_rev(kill - take_pre.sum(-1), r_run)
            r_run = r_run - take_run
            r_todo = r_todo + (take_pre + take_run).sum(-1)
            r_pre_start = jnp.where(r_pre > _EPS, r_pre_start, _INF)
            r_end = jnp.where(r_run > _EPS, r_end, _INF)
            r_demand = jnp.where((red_launch <= t + _EPS) & (r_todo > _EPS),
                                 r_todo, 0.0)
            free_r = red_slots_t - r_run.sum(0) - r_pre.sum(0)
            alloc_r = jnp.where(
                preempt,
                jnp.clip(target - r_run.sum(-1) - r_pre.sum(-1), 0.0,
                         r_demand),
                alloc_free(r_demand, free_r))
        else:
            free_r = red_slots_t - r_run.sum(0) - r_pre.sum(0)
            alloc_r = alloc_free(r_demand, free_r)
        k_r = _by_class(alloc_r, free_r)
        launched_r = k_r > _EPS
        post = launched_r & maps_done[:, None]
        pre = launched_r & ~maps_done[:, None]
        if with_topo:
            # launch-time contention (this wave's transfers included); the
            # bucket keeps its launch-time bandwidth for its whole wave
            shuf_t = shuffle_eff((r_run + r_pre).sum() + k_r.sum())
            red_dur_t = (shuf_t[:, None]
                         + s["red_work"][:, None] / speedup[None, :])
            if with_cloud:
                red_dur_t = inflate(red_dur_t)
        else:
            red_dur_t = red_dur
        r_end = jnp.where(
            post,
            jnp.maximum(jnp.where(r_run > _EPS, r_end, -_INF), t + red_dur_t),
            r_end)
        r_run = jnp.where(post, r_run + k_r, r_run)
        r_pre = jnp.where(pre, r_pre + k_r, r_pre)
        r_pre_start = jnp.where(pre, jnp.minimum(r_pre_start, t), r_pre_start)
        r_todo = r_todo - k_r.sum(-1)

        # (e) autoscaler trigger / teardown (post-allocation, the DES's
        # evaluation points), then advance to the next event
        if with_cloud:
            unmet = (jnp.where(arrived, m_todo, 0.0).sum()
                     + jnp.where(red_launch <= t + _EPS, r_todo, 0.0).sum())
            trigger = ((x_policy > 0.5) & (x_policy < 1.5) & have_extra
                       & (unmet > high_water + _EPS)
                       & (x_on < 0.5) & jnp.isinf(x_at))
            x_at = jnp.where(trigger, t + x_lat, x_at)
            # teardown: nothing queued and the whole block idle (free slots
            # in its class cover it) -> close the billing episode.  The
            # queue policy re-arms on a later burst (x_at back to inf).
            free_m_now = map_slots_t - m_run.sum(0)
            free_r_now = red_slots_t - r_run.sum(0) - r_pre.sum(0)
            drop = ((x_on > 0.5) & (unmet <= _EPS)
                    & (free_m_now[-1] >= x_m - _EPS)
                    & (free_r_now[-1] >= x_r - _EPS))
            ep = t - jnp.where(x_on > 0.5, x_t_on, t)   # 0 when off, no inf
            x_billed = x_billed + jnp.where(drop, _quantize(ep, x_quant), 0.0)
            x_on = jnp.where(drop, 0.0, x_on)
            x_t_on = jnp.where(drop, _INF, x_t_on)

        if with_dag:
            # re-read eligibility off the UPDATED milestones: a job freed
            # by this step's milestones arrives at this same instant, one
            # zero-advance step later; a future release is a scheduled event
            release = release_at(st["release"], map_fin, fin, t)
            elig_next = jnp.maximum(arrival, release)
            released = ((elig_next <= t + _EPS) & ~arrived).any()
        else:
            elig_next = arrival
        t_next = jnp.minimum(
            jnp.where(elig_next > t + _EPS, elig_next, _INF).min(),
            jnp.minimum(m_end.min(), r_end.min()))
        if with_cloud:
            t_next = jnp.minimum(t_next, x_at)
        if with_dag:
            t_next = jnp.where(released, t, t_next)
        t_new = jnp.where(jnp.isfinite(t_next), t_next, t)

        nxt = dict(k=st["k"] + 1, t=t_new, m_todo=m_todo, m_run=m_run,
                   m_end=m_end, r_todo=r_todo, r_run=r_run, r_end=r_end,
                   r_pre=r_pre, r_pre_start=r_pre_start,
                   red_launch=red_launch, map_fin=map_fin, fin=fin)
        if with_cloud:
            nxt.update(x_on=x_on, x_at=x_at, x_t_on=x_t_on, x_billed=x_billed)
        if with_dag:
            nxt.update(release=release,
                       rel=st["rel"] + released.astype(jnp.int32))
        return nxt

    def cont(st):
        # stop at the last event — a frozen scenario pays no further steps
        return (st["k"] < n_steps) & ~jnp.isfinite(st["fin"]).all()

    st = jax.lax.while_loop(cont, step, state0)
    converged = jnp.isfinite(st["fin"]).all()
    fin = st["fin"]
    if with_dag:
        # a DAG child's service clock starts at its release (the DES sets
        # submit_time the same way); double-where: an unreleased child has
        # an infinite release, and inf - inf is the nan this guards against
        submit = jnp.maximum(arrival, st["release"])
        sub_safe = jnp.where(jnp.isfinite(submit), submit, 0.0)
        latency = jnp.where(jnp.isfinite(submit), fin - sub_safe, _INF)
    else:
        latency = fin - arrival
    # nominal busy seconds (baseline-speed work estimate over all slots)
    busy = (n_maps * map_cost + n_reds * (s["shuffle"] + s["red_work"])).sum()
    span = jnp.maximum(fin.max() - arrival.min(), 1e-9)
    # percentile interpolates between sorted neighbours (lo + (hi-lo)*frac);
    # with >= 2 infinite latencies (unconverged scenario) that is inf - inf
    # = nan.  Double-where: the percentile only ever sees finite values, and
    # unconverged scenarios report inf — the same sentinel `finish` uses.
    lat_safe = jnp.where(jnp.isfinite(latency), latency, 0.0)
    out = dict(
        steps=st["k"].astype(jnp.int32),    # loop iterations of this lane
        finish=fin,
        map_finish=st["map_fin"],
        latency=latency,
        converged=converged.astype(jnp.float32),
        mean_latency=latency.mean(),
        p95_latency=jnp.where(
            converged, latency_quantile(lat_safe, 95.0), jnp.inf),
        makespan=span,
        utilization=busy / (span * jnp.maximum(cap_m + cap_r, 1.0)),
    )
    if with_cloud:
        # close a still-open extra-capacity episode at the last finish (the
        # DES closes live online intervals at span the same way); inf for
        # unconverged rows, whose billed seconds are as unknown as their
        # finish times
        x_open = st["x_on"] > 0.5
        fin_max = fin.max()
        # double-where: an unconverged row has inf finish times (and an
        # open episode keeps x_t_on), so the subtraction only ever sees
        # finite operands; the result is overridden to inf below anyway
        end_safe = jnp.where(jnp.isfinite(fin_max), fin_max, 0.0)
        start_safe = jnp.where(jnp.isfinite(st["x_t_on"]), st["x_t_on"], 0.0)
        ep = jnp.where(x_open, jnp.maximum(end_safe - start_safe, 0.0), 0.0)
        billed = st["x_billed"] + jnp.where(x_open, _quantize(ep, x_quant),
                                            0.0)
        out["extra_billed_s"] = jnp.where(converged, billed, jnp.inf)
    if with_dag:
        out["release_steps"] = st["rel"]    # zero-advance release steps
    return out


@functools.lru_cache(maxsize=32)
def _compiled(devs: tuple, n_steps: int, with_fair: bool, with_preempt: bool,
              with_capacity: bool, with_cloud: bool = False,
              with_dag: bool = False, with_topo: bool = False):
    mesh = compat.make_mesh(list(devs), axis="search")

    def per_device(scen, edges):
        return jax.vmap(lambda s: _sim_one(
            s, edges, n_steps, with_fair, with_preempt, with_capacity,
            with_cloud, with_dag, with_topo))(scen)

    # the scenarios are sharded over the devices, the edge tables replicated
    return jax.jit(compat.shard_map(
        per_device, mesh=mesh, in_specs=(P("search"), P()),
        out_specs=P("search"), check_vma=False,
    ))


def _normalize(scen: Mapping[str, np.ndarray]) -> dict[str, np.ndarray]:
    """Canonical scenario batch: legacy aliases resolved, class columns
    2-D and sorted fastest-first, queue columns defaulted."""
    arrs = {k: np.asarray(v) for k, v in scen.items()}
    b = arrs["arrival"].shape[0]
    if "policy" not in arrs:
        arrs["policy"] = arrs.pop("fair") if "fair" in arrs \
            else np.zeros(b, dtype=np.float64)
    arrs.pop("fair", None)
    for k in ("map_slots", "red_slots"):
        if arrs[k].ndim == 1:
            arrs[k] = arrs[k][:, None]
    if "speedup" not in arrs:
        arrs["speedup"] = np.ones_like(arrs["map_slots"])
    elif arrs["speedup"].ndim == 1:
        arrs["speedup"] = arrs["speedup"][:, None]
    # elastic-fleet columns (repro.cloud): default to the fixed fleet.
    # reclaim_rate is per class and must ride the fastest-first re-sort
    # with the slot columns; a 1-D rate applies to every class.
    if "reclaim_rate" not in arrs:
        arrs["reclaim_rate"] = np.zeros(arrs["map_slots"].shape,
                                        dtype=np.float64)
    else:
        rr = np.asarray(arrs["reclaim_rate"], dtype=np.float64)
        if rr.ndim == 1:
            rr = np.repeat(rr[:, None], arrs["map_slots"].shape[1], axis=1)
        arrs["reclaim_rate"] = rr
    for k in ("autoscale", "high_water", "provision_latency",
              "extra_map_slots", "extra_red_slots", "billing_quantum"):
        if k not in arrs:
            arrs[k] = np.zeros(b, dtype=np.float64)
    order = np.argsort(-arrs["speedup"], axis=1, kind="stable")
    for k in ("speedup", "map_slots", "red_slots", "reclaim_rate"):
        arrs[k] = np.take_along_axis(arrs[k], order, axis=1)
    # DAG / topology columns: defaults are the flat no-dependency network,
    # so legacy batches compile the same lean kernels (flag detection below)
    if "dep" not in arrs:
        arrs["dep"] = np.full(arrs["arrival"].shape + (1,), -1, dtype=np.int32)
    if "dep_kind" not in arrs:
        arrs["dep_kind"] = np.zeros(arrs["dep"].shape, dtype=np.int8)
    if "topo_racks" not in arrs:
        arrs["topo_racks"] = np.ones(b, dtype=np.float64)
    if "topo_cross_bw" not in arrs:
        arrs["topo_cross_bw"] = np.full(b, np.inf)
    if "topo_oversub" not in arrs:
        arrs["topo_oversub"] = np.ones(b, dtype=np.float64)
    if "queue" not in arrs:
        arrs["queue"] = np.zeros_like(arrs["arrival"])
    if "queue_frac" not in arrs:
        # default guarantees mirror the DES: equal shares over the queues
        # PRESENT in each row's trace (a single flat 1.0 would hand queue 0
        # a 100% guarantee and starve the rest under the capacity policy)
        qcol = np.round(arrs["queue"]).astype(np.int64)
        n_q = int(qcol.max()) + 1 if qcol.size else 1
        present = (qcol[:, :, None] == np.arange(n_q)[None, None, :]).any(1)
        arrs["queue_frac"] = present / np.maximum(
            present.sum(axis=1, keepdims=True), 1)
    else:
        # guarantees are fractions of the pool: renormalize rows that
        # oversubscribe it so pass-1 capacity fills cannot over-allocate
        qf = arrs["queue_frac"].astype(np.float64)
        tot = qf.sum(axis=1, keepdims=True)
        arrs["queue_frac"] = np.where(tot > 1.0, qf / np.maximum(tot, 1e-9), qf)
    return arrs


def _prepare(scen: Mapping[str, np.ndarray], n_steps: int | None,
             n_devs: int) -> tuple[dict, np.ndarray, int, int, tuple]:
    """The normalized batch padded (edge-replicated) to ``n_devs``, with its
    DAG edges as the distinct (U, J, P) edge tables and each scenario's
    ``edge_id`` into them; its scenario count before padding; and the
    static compile keys of :func:`_compiled`: the step cap and the kernel
    flags."""
    if n_steps is None:
        n_steps = estimate_steps(scen)
    arrs = _normalize(scen)
    b = arrs["arrival"].shape[0]
    pad = (-b) % n_devs
    if pad:
        arrs = {k: np.concatenate([v, np.repeat(v[-1:], pad, axis=0)])
                for k, v in arrs.items()}
    dep = np.asarray(arrs.pop("dep"))
    if dep.dtype.kind == "f":
        dep = np.rint(dep)
    dep = dep.astype(np.int32, copy=False)
    kind = arrs.pop("dep_kind")
    with_dag = bool(np.any(dep >= 0))
    edge_id = np.zeros(len(dep), dtype=np.int32)
    if with_dag:
        # each edge's source in [finish, map-finish]; the scenarios of one
        # trace repeat its table, so the device gets each distinct table once
        src = np.where(dep >= 0, dep + dep.shape[1] * (kind != 0), -1).astype(np.int32)
        ids: dict[bytes, int] = {}
        edge_id[:] = [ids.setdefault(r.tobytes(), len(ids)) for r in src]
        edges = src[np.unique(edge_id, return_index=True)[1]]
    else:
        # one empty table, without a pass over the batch's edge columns
        edges = np.full((1,) + dep.shape[1:], -1, dtype=np.int32)
    arrs["edge_id"] = edge_id
    pol = arrs["policy"]
    with_fair = bool(np.any(pol > 0.5))
    with_preempt = bool(np.any(pol > 1.5))
    with_capacity = bool(np.any(pol > 2.5))
    with_cloud = bool(np.any(arrs["autoscale"] > 0.5)
                      or np.any(arrs["extra_map_slots"] > 0)
                      or np.any(arrs["extra_red_slots"] > 0)
                      or np.any(arrs["reclaim_rate"] > 0))
    with_topo = bool(np.any(
        (arrs["topo_racks"] > 1.5)
        & np.isfinite(arrs["topo_cross_bw"]
                      / np.maximum(arrs["topo_oversub"], 1.0))))
    return arrs, edges, b, n_steps, (with_fair, with_preempt, with_capacity,
                                     with_cloud, with_dag, with_topo)


def simulate_batch(
    scen: Mapping[str, np.ndarray],
    *,
    n_steps: int | None = None,
    devices=None,
) -> dict[str, np.ndarray]:
    """Roll out a batch of scenarios; returns per-scenario metrics plus
    per-job ``finish`` / ``latency`` arrays.  The batch is padded (edge-
    replicated) to the device count and sharded over it.  Policy mix and
    class count are static compile keys: a pure-FIFO homogeneous batch
    compiles the same lean kernel as before the heterogeneity/preemption
    extension (callers split rows by policy, as ``bench_cluster`` does)."""
    devs = tuple(devices) if devices is not None \
        else tuple(compat.default_search_devices())
    ob = _obs_current()
    with ob.span("vector_sim.simulate_batch"):
        with ob.span("vector_sim.prepare"):
            arrs, edges, b, n_steps, flags = _prepare(scen, n_steps, len(devs))
        with ob.span("vector_sim.dispatch", scenarios=b, n_steps=n_steps):
            out = _compiled(devs, n_steps, *flags)(arrs, edges)
        with ob.span("vector_sim.fetch"):
            steps = out.pop("steps")
            rel = out.pop("release_steps", None)
            host = {k: np.asarray(v)[:b] for k, v in out.items()}
            if ob.enabled:
                steps = np.asarray(steps)
                rel = 0 if rel is None else int(np.asarray(rel).sum())
    if ob.enabled:
        reg = ob.registry
        reg.counter("vector_sim.batches").inc()
        reg.counter("vector_sim.scenarios").inc(b)
        reg.counter("vector_sim.scenarios_padded").inc(steps.size - b)
        # every lane of a device's vmapped while_loop runs until that
        # device's slowest lane is done: lane_steps / loop_steps is the
        # share of the loop's lane-steps that did work
        per_dev = steps.reshape(len(devs), -1)
        reg.counter("vector_sim.lane_steps").inc(int(steps.sum()))
        reg.counter("vector_sim.loop_steps").inc(
            per_dev.shape[1] * int(per_dev.max(axis=1).sum()))
        # lane-steps in which the clock stood still to release a DAG job
        reg.counter("vector_sim.release_steps").inc(rel)
    return host
