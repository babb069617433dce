"""Plain reference of the wave-level cluster model behind the capacity
planner, for DAG workloads on a racked network: jobs held by barrier edges
to any number of parents, each reduce wave's shuffle slowed by rack incast,
stepped event by event in numpy float64 (or a lower precision, for the
control run), many scenarios side by side.

The model is :mod:`perfbench.reference.wave_ref`'s, with task times, FIFO
and fair shares taken from there, and these additions, as the planner
states them:

* A job with parents is held until the latest of their finish times.  A
  job freed at an event arrives at that same instant, one step later: the
  clock does not move for that step (a release step).
* Racks: a reduce wave launched at an event pulls its shuffle at the
  per-flow bandwidth of rack incast, counted from the reduce tasks running
  or stalled in the scenario after the launch (the launched ones included).
  With ``R`` racks, uplink capacity ``X`` per rack, oversubscription ``O``
  and ``F`` flows, each flow gets

      bw = min(1, (X / O) / ((R - 1) / R * max(F / R, 1)))

  of its nominal rate where ``R > 1`` and ``X / O`` is finite, else 1; its
  shuffle takes ``shuffle / bw``.  A stalled wave resolves with the
  bandwidth counted at that event, without a launch.
* The next event is the next bucket end, or the same instant where a job
  was freed.

Each row runs to its own end.  Every event retires a bucket of at least
one whole task, admits a job or frees one, so a row ends within its tasks
plus twice its jobs; a row still running at twice that is stopped as not
converged (a guard against a fault here, which no sound row reaches).  So
is a row whose clock stands still for more than four times its jobs plus
64 steps in a row: only a release holds the clock in exact arithmetic, at
most once a job, and a task shorter than the clock's spacing holds it for
a step; a precision too coarse for the clock (bfloat16 past about 10^4 s,
in the control) rounds every task end to the present and would step once
a task."""

from __future__ import annotations

import numpy as np

from .wave_ref import EPS, _fair, _fifo

__all__ = ["incast_bandwidth", "simulate", "simulate_makespan"]


def incast_bandwidth(racks, cross_bw, oversub, flows, dt):
    """Per-flow bandwidth of ``flows`` concurrent shuffle flows under rack
    incast, in units of the nominal rate (module docstring), each ``(R,)``."""
    racks = np.maximum(racks, dt(1))
    cap = (cross_bw / np.maximum(oversub, dt(1))).astype(dt)
    cross = ((racks - dt(1)) / racks).astype(dt)
    demand = (cross * np.maximum((flows / racks).astype(dt), dt(1))).astype(dt)
    contended = (racks > 1.5) & (demand > 0) & np.isfinite(cap)
    with np.errstate(divide="ignore", invalid="ignore"):
        share = np.where(contended, (cap / np.where(contended, demand, dt(1))).astype(dt), dt(1))
    return np.minimum(share, dt(1)).astype(dt)


def simulate(arrival, n_maps, n_reds, map_dur, shuffle, red_work, map_slots, red_slots,
             fair, slowstart, racks, cross_bw, oversub, dep, dtype=np.float64):
    """Per-job finish times ``(R, J)`` of ``R`` scenarios (inf where a row
    did not converge: it ran past the guard of the module docstring),
    whether each converged, and whether a task of it ever waited for a
    slot, each ``(R,)``.  ``dep`` is ``(J, P)``: each job's parents by
    position (lower than its own), -1 for none; ``racks``, ``cross_bw`` and
    ``oversub`` are the network's, per scenario.

    Only the rows still running take a step, and only over the jobs from
    the first one not finished in every row to the last one whose latest
    parent has finished in some row: the jobs outside take and free no
    slot and hold no event."""
    dt = np.dtype(dtype).type
    A = lambda x: np.array(x, dtype=dt)      # noqa: E731
    C = lambda x: x.astype(dt, copy=False)   # noqa: E731
    eps, inf, ninf, zero = dt(EPS), dt(np.inf), dt(-np.inf), dt(0)
    tasks = np.asarray(n_maps, dtype=np.float64) + np.asarray(n_reds, dtype=np.float64)
    arrival, n_maps, n_reds = A(arrival), A(n_maps), A(n_reds)
    R, J = arrival.shape
    guard = 2 * (tasks.sum(axis=1) + 2 * J) + 64
    dep = np.asarray(dep)
    has_parent = dep >= 0
    parent = np.where(has_parent, dep, 0)
    last_parent = dep.max(axis=1)
    rows = dict(arrival=arrival, n_maps=n_maps, n_reds=n_reds, map_dur=A(map_dur),
                shuffle=A(shuffle), red_work=A(red_work),
                slow_need=C(A(slowstart)[:, None] * n_maps))
    cols = dict(map_slots=A(map_slots), red_slots=A(red_slots), racks=A(racks),
                cross_bw=A(cross_bw), oversub=A(oversub), fair=np.asarray(fair) > 0.5,
                guard=guard)
    state = dict(m_todo=n_maps.copy(), m_run=np.zeros_like(arrival),
                 m_end=np.full_like(arrival, inf), r_todo=n_reds.copy(),
                 r_run=np.zeros_like(arrival), r_end=np.full_like(arrival, inf),
                 r_pre=np.zeros_like(arrival), r_pre_start=np.full_like(arrival, inf),
                 red_launch=np.full_like(arrival, inf), map_fin=np.full_like(arrival, inf),
                 fin=np.full_like(arrival, inf))
    t = arrival.min(axis=1)
    waited = np.zeros(R, dtype=bool)
    still = np.zeros(R, dtype=np.int64)      # steps since the clock last moved
    live = np.arange(R)                      # positions of the rows still running
    fin_all = np.full((R, J), inf)
    converged = np.zeros(R, dtype=bool)
    waited_all = np.zeros(R, dtype=bool)

    def eligible(fin, lo, hi):
        held = np.where(has_parent[lo:hi], fin[:, parent[lo:hi]], ninf).max(axis=2)
        return np.maximum(rows["arrival"][:, lo:hi], held)

    def window(fin):
        running = ~np.isfinite(fin).all(axis=0)
        lo = int(np.argmax(running)) if running.any() else J
        free = (last_parent < 0) | np.isfinite(fin[:, np.maximum(last_parent, 0)]).any(axis=0)
        hi = J - int(np.argmax(free[::-1])) if free.any() else 0
        return lo, max(lo, hi)

    lo, hi = window(state["fin"])
    elig = np.full((R, J), inf)
    elig[:, lo:hi] = eligible(state["fin"], lo, hi)
    for step in range(int(cols["guard"].max()) + 1):
        fin = state["fin"]
        done_rows = np.isfinite(fin).all(axis=1)
        over = done_rows | (step >= cols["guard"]) | (still > 4 * J + 64)
        if over.any():
            fin_all[live[over]] = fin[over]
            converged[live[over]] = done_rows[over]
            waited_all[live[over]] = waited[over]
            keep = ~over
            live = live[keep]
            if not live.size:
                break
            t, waited, elig, still = t[keep], waited[keep], elig[keep], still[keep]
            state = {k: v[keep] for k, v in state.items()}
            rows = {k: v[keep] for k, v in rows.items()}
            cols = {k: v[keep] for k, v in cols.items()}
            lo, hi = window(state["fin"])
        w = slice(lo, hi)
        m_todo, m_run, m_end, r_todo, r_run, r_end, r_pre, r_pre_start, red_launch, \
            map_fin, fin = (state[k][:, w] for k in (
                "m_todo", "m_run", "m_end", "r_todo", "r_run", "r_end", "r_pre",
                "r_pre_start", "red_launch", "map_fin", "fin"))
        n_maps_w, n_reds_w = rows["n_maps"][:, w], rows["n_reds"][:, w]
        shuffle, red_work = rows["shuffle"][:, w], rows["red_work"][:, w]
        T = t[:, None]
        at = C(T + eps)
        arrived = elig[:, w] <= at
        # buckets due complete
        done = (m_run > eps) & (m_end <= at)
        m_run, m_end = np.where(done, zero, m_run), np.where(done, inf, m_end)
        done = (r_run > eps) & (r_end <= at)
        r_run, r_end = np.where(done, zero, r_run), np.where(done, inf, r_end)
        # milestones
        maps_done = arrived & (m_todo <= eps) & (m_run <= eps)
        first = np.isinf(map_fin) & maps_done
        map_fin = np.where(first, T, map_fin)
        slow_ok = arrived & (C(n_maps_w - m_todo - m_run) >= rows["slow_need"][:, w] - eps)
        red_launch = np.where(np.isinf(red_launch) & slow_ok, T, red_launch)
        stalled = first & (r_pre > eps)
        if stalled.any():
            bw = incast_bandwidth(cols["racks"], cols["cross_bw"], cols["oversub"],
                                  C((r_run + r_pre).sum(axis=1)), dt)
            shuf = C(shuffle / np.maximum(bw, dt(1e-9))[:, None])
            ends = C(np.maximum(map_fin, C(r_pre_start + shuf)) + red_work)
            r_end = np.where(stalled, np.maximum(np.where(r_run > eps, r_end, ninf), ends),
                             r_end)
            r_run = np.where(stalled, C(r_run + r_pre), r_run)
            r_pre = np.where(stalled, zero, r_pre)
            r_pre_start = np.where(stalled, inf, r_pre_start)
        reds_done = (r_todo <= eps) & (r_run <= eps) & (r_pre <= eps)
        finished = arrived & maps_done & np.where(n_reds_w > 0, reds_done, True)
        newly = np.isinf(fin) & finished
        fin = np.where(newly, T, fin)
        fair = cols["fair"]

        def share(want, free):
            got = _fifo(want, free)
            if fair.any():
                got[fair] = _fair(want[fair], free[fair], dt)
            return C(got)

        # map slots
        want = np.where(arrived & (m_todo > eps), m_todo, zero)
        got = share(want, C(cols["map_slots"] - m_run.sum(axis=1)))
        waited |= (want - got > eps).any(axis=1)
        launched = got > eps
        m_end = np.where(launched, np.maximum(np.where(m_run > eps, m_end, ninf),
                                              C(T + rows["map_dur"][:, w])), m_end)
        m_run, m_todo = C(m_run + got), C(m_todo - got)
        # reduce slots, at the incast bandwidth after the launch
        want = np.where((red_launch <= at) & (r_todo > eps), r_todo, zero)
        got = share(want, C(cols["red_slots"] - r_run.sum(axis=1) - r_pre.sum(axis=1)))
        waited |= (want - got > eps).any(axis=1)
        launched = got > eps
        if launched.any():
            post, pre = launched & maps_done, launched & ~maps_done
            bw = incast_bandwidth(cols["racks"], cols["cross_bw"], cols["oversub"],
                                  C((r_run + r_pre).sum(axis=1) + got.sum(axis=1)), dt)
            red_dur = C(C(shuffle / np.maximum(bw, dt(1e-9))[:, None]) + red_work)
            r_end = np.where(post, np.maximum(np.where(r_run > eps, r_end, ninf),
                                              C(T + red_dur)), r_end)
            r_run = np.where(post, C(r_run + got), r_run)
            r_pre = np.where(pre, C(r_pre + got), r_pre)
            r_pre_start = np.where(pre, np.minimum(r_pre_start, T), r_pre_start)
            r_todo = C(r_todo - got)
        for k, v in (("m_todo", m_todo), ("m_run", m_run), ("m_end", m_end),
                     ("r_todo", r_todo), ("r_run", r_run), ("r_end", r_end),
                     ("r_pre", r_pre), ("r_pre_start", r_pre_start),
                     ("red_launch", red_launch), ("map_fin", map_fin), ("fin", fin)):
            state[k][:, w] = v
        # next event: the same instant where a job was freed
        freed = np.zeros(len(live), dtype=bool)
        if newly.any():
            lo, hi = window(state["fin"])
            new = eligible(state["fin"], lo, hi)
            freed = ((new <= at) & (elig[:, lo:hi] > at)).any(axis=1)
            elig[:, lo:hi] = new
        ahead = np.where(elig[:, lo:hi] > at, elig[:, lo:hi], inf)
        nxt = np.minimum(ahead.min(axis=1, initial=inf),
                         np.minimum(state["m_end"][:, lo:hi].min(axis=1, initial=inf),
                                    state["r_end"][:, lo:hi].min(axis=1, initial=inf)))
        nxt = np.where(freed, t, nxt)
        t_new = np.where(np.isfinite(nxt), nxt, t)
        still = np.where(t_new == t, still + 1, 0)
        t = t_new
    return np.where(converged[:, None], fin_all, inf), converged, waited_all


def simulate_makespan(task):
    """``(scenario columns, dtype name)`` -> (makespan in float64,
    converged, waited, the last finish time) of each scenario; a worker
    process's unit of work.  The makespan is the first arrival to the last
    finish."""
    sc, dtype = task
    if dtype == "bfloat16":
        import ml_dtypes

        dtype = ml_dtypes.bfloat16
    fin, conv, waited = simulate(**sc, dtype=dtype)
    first = np.asarray(sc["arrival"], dtype=fin.dtype).min(axis=1)
    span = (fin.max(axis=1) - first).astype(fin.dtype)
    last = np.where(conv, fin.max(axis=1), 0).astype(np.float64)
    return span.astype(np.float64), conv, waited, last
