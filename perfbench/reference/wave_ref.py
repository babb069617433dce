"""Plain reference of the wave-level cluster model behind the capacity
planner: jobs of a trace on a cluster of one node class, FIFO or fair slot
sharing, a reduce slowstart, stepped event by event in numpy float64 (or a
lower precision, for the control run), many scenarios side by side.

Task times come from the float64 oracle
(:mod:`perfbench.reference.hadoop_ref`) of each job type on a cluster of the
row's node count: map I/O plus CPU, reduce I/O plus CPU, and each reducer's
share of the network transfer (Eqs. 90-91).

The model, as the planner states it:

* At each event time the wave buckets due then complete.  Then each job's
  milestones move: its maps all done, its slowstart fraction of maps done,
  the job finished.  Then the free map slots, and after them the free
  reduce slots, go to the jobs that want them.
* FIFO hands free slots out in arrival order.  Fair gives every job that
  wants slots an equal whole share, ``floor(free / wanting)``, at most what
  it wants, and hands what is left out in arrival order.
* The tasks a job launches at an event join its running bucket of that
  kind, which ends one task duration after its latest launch.
* A reduce wave launched before its job's maps are done stalls; when they
  are, it ends at ``max(maps done, launch + shuffle) + reduce work``.  One
  launched after ends a shuffle plus the reduce work after its launch.
* The next event is the next arrival or the next bucket end; times within
  ``EPS`` of an event count as at it.

A scenario not finished within its cap of events has not converged; the
planner marks such a row invalid and the comparison does the same."""

from __future__ import annotations

import functools

import numpy as np

from .hadoop_params import CostFactors, HadoopParams, ProfileStats
from .hadoop_ref import job_model

__all__ = ["EPS", "task_times", "step_cap", "simulate", "p95", "simulate_p95"]

EPS = 1e-3


@functools.lru_cache(maxsize=4096)
def task_times(params: HadoopParams, stats: ProfileStats, costs: CostFactors,
               nodes: int) -> tuple[float, float, float]:
    """(map task seconds, reducer shuffle seconds, reduce work seconds) of one
    job type on ``nodes`` nodes."""
    j = job_model(params.replace(pNumNodes=nodes), stats, costs)
    mc = j.map.ioCost + j.map.cpuCost
    if params.pNumReducers == 0:
        return mc, 0.0, 0.0
    return mc, j.netCost / params.pNumReducers, j.reduce.ioCost + j.reduce.cpuCost


def step_cap(n_maps, n_reds, map_slots, red_slots) -> int:
    """The planner's event cap for one chunk of scenarios (``(R, J)`` task
    counts, ``(R,)`` slots): twice the most waves any scenario needs if no
    job shared a slot, plus one event per job and 8, rounded up to a power
    of two."""
    ms = np.maximum(np.asarray(map_slots, dtype=np.float64), 1.0)[:, None]
    rs = np.maximum(np.asarray(red_slots, dtype=np.float64), 1.0)[:, None]
    waves = np.ceil(n_maps / ms).sum(axis=1) + np.ceil(n_reds / rs).sum(axis=1)
    est = int(np.max(waves) * 2.0) + n_maps.shape[1] + 8
    return 1 << (est - 1).bit_length()


def _fifo(want, free):
    before = np.cumsum(want, axis=1) - want
    return np.clip(free[:, None] - before, 0, want)


def _fair(want, free, dt):
    wanting = (want > EPS).sum(axis=1)
    share = np.floor(free / np.maximum(wanting, 1).astype(dt) + dt(EPS))
    got = np.minimum(want, share[:, None])
    need = want - got
    left = np.floor(free - got.sum(axis=1) + dt(EPS))
    return got + np.clip(left[:, None] - (np.cumsum(need, axis=1) - need), 0, need)


def simulate(arrival, n_maps, n_reds, map_dur, shuffle, red_work, map_slots, red_slots,
             fair, slowstart, n_steps, dtype=np.float64):
    """Per-job latencies ``(R, J)`` of ``R`` scenarios, whether each
    converged within its ``n_steps`` events (a cap for all, or one per
    scenario), and whether a task of it ever waited for a slot, each
    ``(R,)``.  Jobs are in arrival order; ``arrival``, ``map_dur``,
    ``shuffle`` and ``red_work`` are seconds, ``fair`` is 1 for fair sharing
    and 0 for FIFO."""
    dt = np.dtype(dtype).type
    A = lambda x: np.array(x, dtype=dt)      # noqa: E731
    arrival, n_maps, n_reds = A(arrival), A(n_maps), A(n_reds)
    map_dur, shuffle, red_work = A(map_dur), A(shuffle), A(red_work)
    red_dur = (shuffle + red_work).astype(dt)
    map_slots, red_slots = A(map_slots), A(red_slots)
    is_fair = (np.asarray(fair) > 0.5)[:, None]
    slow_need = (A(slowstart)[:, None] * n_maps).astype(dt)
    eps, inf, ninf, zero = dt(EPS), dt(np.inf), dt(-np.inf), dt(0)

    t = arrival.min(axis=1)
    m_todo, m_run, m_end = n_maps.copy(), np.zeros_like(arrival), np.full_like(arrival, inf)
    r_todo, r_run, r_end = n_reds.copy(), np.zeros_like(arrival), np.full_like(arrival, inf)
    r_pre, r_pre_start = np.zeros_like(arrival), np.full_like(arrival, inf)
    red_launch, map_fin, fin = (np.full_like(arrival, inf) for _ in range(3))

    def share(want, free):
        return np.where(is_fair, _fair(want, free, dt), _fifo(want, free)).astype(dt)

    caps = np.broadcast_to(np.asarray(n_steps), t.shape)
    used = np.full(t.shape, np.iinfo(np.int64).max)     # events until finished
    waited = np.zeros(t.shape, dtype=bool)
    for step in range(int(caps.max())):
        over = np.isfinite(fin).all(axis=1)
        used = np.where(over & (used > step), step, used)
        if over.all():
            break
        T = t[:, None]
        at = (T + eps).astype(dt)
        arrived = arrival <= at
        # buckets due complete
        done = (m_run > eps) & (m_end <= at)
        m_run, m_end = np.where(done, zero, m_run), np.where(done, inf, m_end)
        done = (r_run > eps) & (r_end <= at)
        r_run, r_end = np.where(done, zero, r_run), np.where(done, inf, r_end)
        # milestones
        maps_done = arrived & (m_todo <= eps) & (m_run <= eps)
        first = np.isinf(map_fin) & maps_done
        map_fin = np.where(first, T, map_fin)
        slow_ok = arrived & ((n_maps - m_todo - m_run).astype(dt) >= slow_need - eps)
        red_launch = np.where(np.isinf(red_launch) & slow_ok, T, red_launch)
        stalled = first & (r_pre > eps)
        ends = (np.maximum(map_fin, (r_pre_start + shuffle).astype(dt)) + red_work).astype(dt)
        r_end = np.where(stalled, np.maximum(np.where(r_run > eps, r_end, ninf), ends), r_end)
        r_run = np.where(stalled, (r_run + r_pre).astype(dt), r_run)
        r_pre = np.where(stalled, zero, r_pre)
        r_pre_start = np.where(stalled, inf, r_pre_start)
        reds_done = (r_todo <= eps) & (r_run <= eps) & (r_pre <= eps)
        finished = arrived & maps_done & np.where(n_reds > 0, reds_done, True)
        fin = np.where(np.isinf(fin) & finished, T, fin)
        # map slots
        want = np.where(arrived & (m_todo > eps), m_todo, zero)
        got = share(want, (map_slots - m_run.sum(axis=1)).astype(dt))
        waited |= (want - got > eps).any(axis=1)
        launched = got > eps
        m_end = np.where(launched, np.maximum(np.where(m_run > eps, m_end, ninf),
                                              (T + map_dur).astype(dt)), m_end)
        m_run, m_todo = (m_run + got).astype(dt), (m_todo - got).astype(dt)
        # reduce slots
        want = np.where((red_launch <= at) & (r_todo > eps), r_todo, zero)
        got = share(want, (red_slots - r_run.sum(axis=1) - r_pre.sum(axis=1)).astype(dt))
        waited |= (want - got > eps).any(axis=1)
        launched = got > eps
        post, pre = launched & maps_done, launched & ~maps_done
        r_end = np.where(post, np.maximum(np.where(r_run > eps, r_end, ninf),
                                          (T + red_dur).astype(dt)), r_end)
        r_run = np.where(post, (r_run + got).astype(dt), r_run)
        r_pre = np.where(pre, (r_pre + got).astype(dt), r_pre)
        r_pre_start = np.where(pre, np.minimum(r_pre_start, T), r_pre_start)
        r_todo = (r_todo - got).astype(dt)
        # next event
        nxt = np.minimum(np.where(arrival > at, arrival, inf).min(axis=1),
                         np.minimum(m_end.min(axis=1), r_end.min(axis=1)))
        t = np.where(np.isfinite(nxt), nxt, t)
    else:
        over = np.isfinite(fin).all(axis=1)
        used = np.where(over & (used > caps.max()), caps.max(), used)
    converged = used <= caps
    return np.where(converged[:, None], (fin - arrival).astype(dt), inf), converged, waited


def p95(lat) -> np.ndarray:
    """95th percentile of each row, linear between order statistics."""
    v = np.sort(lat, axis=1)
    rank = (v.shape[1] - 1) * 0.95
    lo = int(np.floor(rank))
    hi = min(lo + 1, v.shape[1] - 1)
    a, b = v[:, lo], v[:, hi]
    frac = v.dtype.type(rank - lo)
    with np.errstate(invalid="ignore"):
        return np.where(a == b, a, (a + (b - a) * frac).astype(v.dtype))


def simulate_p95(task):
    """``(scenario columns, caps, dtype name)`` -> (p95 latency in float64,
    converged, waited, the last finish time) of each scenario; a worker
    process's unit of work."""
    sc, caps, dtype = task
    if dtype == "bfloat16":
        import ml_dtypes

        dtype = ml_dtypes.bfloat16
    lat, conv, waited = simulate(**sc, n_steps=caps, dtype=dtype)
    last = np.max(np.asarray(sc["arrival"], dtype=np.float64)
                  + np.where(np.isfinite(lat), lat, 0).astype(np.float64), axis=1)
    return p95(lat).astype(np.float64), conv, waited, last
