"""repro.cluster — multi-job DES, vectorized wave simulator, planner."""

import numpy as np
import pytest

from repro.cluster import (
    ClusterConfig,
    ClusterEvaluator,
    JobArrival,
    JobClass,
    NodeClass,
    UnfinishedWorkloadError,
    WorkloadTrace,
    bursty_trace,
    default_job_classes,
    estimate_steps,
    pack_trace,
    poisson_trace,
    rescale,
    simulate_batch,
    simulate_workload,
)
from repro.cluster.workload import task_costs
from repro.core.hadoop.params import CostFactors, HadoopParams, MiB, ProfileStats
from repro.core.hadoop.simulator import SimConfig, simulate_job
from repro.search import WhatIfService, grid_search_ev, search_topk

CLASSES = default_job_classes()
CLEAN = SimConfig(speculative_execution=False)
NOISY = SimConfig(seed=11, task_time_jitter=0.2, straggler_prob=0.1)


def scenario_for(trace, cc: ClusterConfig, rate: float, fair: float = 0.0,
                 *, policy: float | None = None,
                 queue_frac: list | None = None):
    """Wave-model scenario mirroring ``cc`` (including a heterogeneous
    ``node_classes`` fleet as per-class slot columns, fastest first)."""
    cols = pack_trace(trace)
    n = cc.num_nodes
    fleet = sorted(cc.node_classes, key=lambda nc: -nc.speedup) \
        or [NodeClass(n, 1.0)]
    scen = {
        "arrival": (cols["arrival"] / rate)[None, :],
        "n_maps": cols["n_maps"][None, :],
        "n_reds": cols["n_reds"][None, :],
        "map_cost": cols["map_cost"][None, :],
        "red_work": cols["red_work"][None, :],
        "shuffle": (cols["shuffle"] * (n - 1) / n)[None, :],
        "queue": cols["queue"][None, :],
        "map_slots": np.array(
            [[float(nc.count * cc.map_slots_per_node) for nc in fleet]]),
        "red_slots": np.array(
            [[float(nc.count * cc.reduce_slots_per_node) for nc in fleet]]),
        "speedup": np.array([[nc.speedup for nc in fleet]]),
        "policy": np.array([float(fair) if policy is None else float(policy)]),
        "slowstart": np.array([cc.reduce_slowstart]),
    }
    if queue_frac is not None:
        scen["queue_frac"] = np.array([queue_frac], dtype=np.float64)
    return scen


# ------------------------------------------------------------------ workload


def test_traces_sorted_and_rescaled():
    tr = poisson_trace(CLASSES, 16, rate=1.0, seed=3)
    times = tr.submit_times
    assert tr.n_jobs == 16 and times[0] == 0.0
    assert np.all(np.diff(times) >= 0)
    fast = rescale(tr, 4.0)
    assert np.allclose(fast.submit_times, times / 4.0)
    with pytest.raises(ValueError):
        rescale(tr, 0.0)


def test_bursty_trace_shape():
    tr = bursty_trace(CLASSES, n_bursts=3, burst_size=4, burst_gap=50.0)
    assert tr.n_jobs == 12
    # each burst's jobs land within one intra-gap window of each other
    t = tr.submit_times.reshape(3, 4)
    assert np.all(t[:, -1] - t[:, 0] < 50.0)


# ------------------------------------------------------------- multi-job DES


def test_single_job_trace_reproduces_simulate_job():
    """One job on the shared cluster == the single-job simulator, exactly —
    including under jitter, stragglers and speculation (same RNG draws)."""
    p = HadoopParams(pNumNodes=4, pNumMappers=32, pNumReducers=8,
                     pSplitSize=64 * MiB)
    jc = JobClass("one", p, ProfileStats(), CostFactors())
    tr = WorkloadTrace((JobArrival(0, jc, 0.0),))
    for sim in (CLEAN, NOISY, SimConfig(seed=2, task_time_jitter=0.3)):
        ref = simulate_job(p, ProfileStats(), CostFactors(), sim)
        got = simulate_workload(tr, ClusterConfig.from_params(p), sim)
        assert got.jobs[0].finish == ref.makespan
        assert got.jobs[0].map_finish == ref.map_finish_time
        assert got.num_speculative_launched == ref.num_speculative_launched


def test_workload_deterministic_and_seed_sensitive():
    tr = rescale(poisson_trace(CLASSES, 10, seed=4), 0.1)
    a = simulate_workload(tr, ClusterConfig(), NOISY)
    b = simulate_workload(tr, ClusterConfig(), NOISY)
    assert a.latencies().tolist() == b.latencies().tolist()
    assert len(a.records) == len(b.records)
    c = simulate_workload(tr, ClusterConfig(), SimConfig(
        seed=NOISY.seed + 1, task_time_jitter=0.2, straggler_prob=0.1))
    assert a.latencies().tolist() != c.latencies().tolist()


def test_all_jobs_complete_and_accounting():
    tr = rescale(poisson_trace(CLASSES, 12, seed=5), 0.2)
    r = simulate_workload(tr, ClusterConfig(num_nodes=4), CLEAN)
    assert all(np.isfinite(j.finish) for j in r.jobs)
    assert all(j.queueing_delay >= 0 and j.latency > 0 for j in r.jobs)
    assert len(r.node_busy_s) == 4
    assert 0 < r.slot_utilization <= 1
    # busy time equals the sum of record occupancy
    assert sum(r.node_busy_s) == pytest.approx(
        sum(rec.end - rec.start for rec in r.records))


def test_fair_share_protects_small_job_behind_big_one():
    """FIFO invariant: a small job queued behind a big one waits; fair-share
    gives it a share of the slots immediately."""
    big = JobClass("big", HadoopParams(pNumMappers=64, pNumReducers=8,
                                       pSplitSize=64 * MiB),
                   ProfileStats(), CostFactors())
    small = JobClass("small", HadoopParams(pNumMappers=4, pNumReducers=1,
                                           pSplitSize=64 * MiB),
                     ProfileStats(), CostFactors())
    tr = WorkloadTrace((JobArrival(0, big, 0.0), JobArrival(1, small, 1.0)))
    fifo = simulate_workload(tr, ClusterConfig(num_nodes=2), CLEAN)
    fair = simulate_workload(
        tr, ClusterConfig(num_nodes=2, scheduler="fair"), CLEAN)
    assert fair.jobs[1].latency < fifo.jobs[1].latency
    # work conservation: both policies complete both jobs
    assert all(np.isfinite(j.finish) for j in fifo.jobs + fair.jobs)


def test_node_failure_requeues_across_jobs():
    tr = rescale(poisson_trace(CLASSES, 6, seed=6), 0.05)
    base = simulate_workload(tr, ClusterConfig(), CLEAN)
    # t=1.0: the first job's map fleet (>= 16 tasks on 8 slots) is still
    # occupying every node, so the failure must kill in-flight work
    failed = simulate_workload(
        tr, ClusterConfig(),
        SimConfig(speculative_execution=False, node_failures=((1.0, 0),)))
    assert failed.num_failure_reruns > 0
    assert all(np.isfinite(j.finish) for j in failed.jobs)
    assert failed.makespan >= base.makespan


# ------------------------------------------------- DES <-> vectorized rollout


@pytest.mark.parametrize("label,nodes,rate", [
    ("serialized", 4, 0.002),
    ("uncontended", 64, 0.1),
    ("contended", 4, 0.1),
    ("heavy", 2, 0.5),
])
def test_vector_sim_matches_des_fifo(label, nodes, rate):
    """Wave rollout vs DES per-job finish times (exact wave structure on
    contention-free FIFO; the contended rows document that the wave-merge
    approximation stays tight on these workloads)."""
    tr = poisson_trace(CLASSES, 10, rate=1.0, seed=1)
    cc = ClusterConfig(num_nodes=nodes)
    des = simulate_workload(rescale(tr, rate), cc, CLEAN)
    out = simulate_batch(scenario_for(tr, cc, rate))
    assert out["converged"][0] == 1.0
    des_fin = np.array([j.finish for j in des.jobs])
    np.testing.assert_allclose(out["finish"][0], des_fin, rtol=1e-3)
    assert out["p95_latency"][0] == pytest.approx(des.p95_latency, rel=1e-3)


def test_vector_sim_property_uncontended_agreement():
    """Property test: random uncontended FIFO scenarios agree with the DES
    (slots cover every job's full parallelism, so waves never fragment)."""
    hypothesis = pytest.importorskip(
        "hypothesis", reason="property tests need hypothesis")
    from hypothesis import given, settings
    from hypothesis import strategies as st

    # slowstart floor at 0.01: with ss == 0 exactly, the DES launches
    # reducers at the first map *completion* (its check runs on completion
    # events) while the wave model launches at arrival — a documented
    # granularity edge, not a wave-structure bug
    @settings(max_examples=20, deadline=None)
    @given(seed=st.integers(0, 1000), rate=st.floats(0.01, 0.5),
           n_jobs=st.integers(2, 8), slowstart=st.floats(0.01, 1.0))
    def check(seed, rate, n_jobs, slowstart):
        tr = poisson_trace(CLASSES, n_jobs, rate=1.0, seed=seed)
        # uncontended: slots cover every job's full parallelism at once
        need = max(sum(a.klass.n_maps for a in tr.arrivals),
                   sum(a.klass.n_reduces for a in tr.arrivals), 1)
        nodes = -(-need // 2)
        cc = ClusterConfig(num_nodes=nodes, reduce_slowstart=slowstart)
        des = simulate_workload(rescale(tr, rate), cc, CLEAN)
        out = simulate_batch(scenario_for(tr, cc, rate))
        assert out["converged"][0] == 1.0
        des_fin = np.array([j.finish for j in des.jobs])
        np.testing.assert_allclose(out["finish"][0], des_fin, rtol=2e-3)

    check()


def test_vector_sim_fair_converges_and_orders():
    tr = poisson_trace(CLASSES, 12, rate=1.0, seed=2)
    cc = ClusterConfig(num_nodes=2)
    out = simulate_batch(scenario_for(tr, cc, 0.5, fair=1.0))
    assert out["converged"][0] == 1.0
    assert np.isfinite(out["p95_latency"][0])


def test_simulate_batch_refuses_an_unknown_column():
    """A ``fair`` column, no longer an alias of ``policy``, is refused by
    name instead of silently running the batch FIFO."""
    tr = poisson_trace(CLASSES, 4, rate=1.0, seed=0)
    scen = scenario_for(tr, ClusterConfig(num_nodes=2), 0.5)
    scen["fair"] = scen.pop("policy") + 1.0
    with pytest.raises(ValueError, match="'fair'"):
        simulate_batch(scen)


def test_truncation_is_flagged_not_silent():
    tr = poisson_trace(CLASSES, 8, rate=1.0, seed=0)
    out = simulate_batch(scenario_for(tr, ClusterConfig(num_nodes=2), 0.5),
                         n_steps=4)
    assert out["converged"][0] == 0.0


def test_estimate_steps_power_of_two():
    tr = poisson_trace(CLASSES, 8, rate=1.0, seed=0)
    scen = scenario_for(tr, ClusterConfig(), 0.1)
    n = estimate_steps(scen)
    assert n & (n - 1) == 0 and n > 0


def _many_jobs_at_once(n_jobs, n_maps, slots, policy):
    """``n_jobs`` map-only jobs of ``n_maps`` tasks each, all submitted at 0
    on ``slots`` map slots, with seeded task times of 1 to 3 s."""
    rng = np.random.default_rng(0)
    zeros = np.zeros((1, n_jobs))
    return {"arrival": zeros, "n_maps": np.full((1, n_jobs), float(n_maps)),
            "n_reds": zeros, "map_cost": rng.uniform(1.0, 3.0, (1, n_jobs)),
            "red_work": zeros, "shuffle": zeros, "map_slots": np.asarray([float(slots)]),
            "red_slots": np.asarray([4.0]), "policy": np.asarray([float(policy)]),
            "slowstart": np.ones(1)}


@pytest.mark.parametrize("policy", [0, 1])
@pytest.mark.parametrize("n_jobs,n_maps,slots", [(8, 100, 8), (14, 400, 16)])
def test_estimate_steps_covers_fair_sharing_among_many_jobs(n_jobs, n_maps, slots, policy):
    # fair shares split each job's waves over a slice of the slots: these
    # rows take more steps than twice their waves on the whole pool (the
    # cap before), which truncated them
    scen = _many_jobs_at_once(n_jobs, n_maps, slots, policy)
    waves = n_jobs * -(-n_maps // slots)
    old_cap = 1 << (2 * waves + n_jobs + 8 - 1).bit_length()
    out = simulate_batch(scen)
    assert out["converged"][0] == 1.0
    ref = simulate_batch(scen, n_steps=1 << 16)
    np.testing.assert_array_equal(out["finish"], ref["finish"])
    assert estimate_steps(scen) >= n_jobs * n_maps
    if policy == 1:
        assert simulate_batch(scen, n_steps=old_cap)["converged"][0] == 0.0


# ------------------------------------------------------------------ planner


@pytest.fixture(scope="module")
def evaluator():
    return ClusterEvaluator(CLASSES, n_jobs=10, n_seeds=2, chunk=16,
                            base_rate=0.05, objective="p95")


def test_evaluator_monotone_in_capacity(evaluator):
    res = evaluator.evaluate({"pNumNodes": np.array([2.0, 4.0, 8.0, 16.0])})
    assert res.outputs["valid"].all()
    assert np.all(np.diff(res.total_cost) <= 1e-3)      # more nodes, no worse
    assert np.all(np.diff(res.outputs["w_util"]) < 0)   # ... less utilized


def test_evaluator_exact_cost_close_on_light_load(evaluator):
    vec = float(evaluator.evaluate({"pNumNodes": np.array([16.0])}).total_cost[0])
    des = evaluator.exact_cost({"pNumNodes": 16.0})
    assert vec == pytest.approx(des, rel=0.05)


def test_evaluator_invalid_rows(evaluator):
    res = evaluator.evaluate({"pNumNodes": np.array([0.0, 4.0])})
    assert res.outputs["valid"][0] == 0.0 and np.isinf(res.total_cost[0])
    assert res.outputs["valid"][1] == 1.0
    assert evaluator.exact_cost({"pNumNodes": 0.0}) == np.inf
    # a zero-slot row is masked invalid AND must not stall the chunk's
    # shared while_loop (its lane simulates sanitized knobs instead)
    res2 = evaluator.evaluate({"pMaxMapsPerNode": np.array([0.0, 2.0])})
    assert res2.outputs["valid"][0] == 0.0 and np.isinf(res2.total_cost[0])
    assert res2.outputs["valid"][1] == 1.0 and np.isfinite(res2.total_cost[1])


def test_grid_search_and_topk_end_to_end(evaluator):
    space = {"pNumNodes": [2.0, 4.0, 8.0], "schedPolicy": [0.0, 1.0]}
    plan = grid_search_ev(evaluator, space)
    assert np.isfinite(plan.best_cost) and plan.evaluations == 6
    assert set(plan.best_assignment) == set(space)
    top = search_topk(evaluator, space, k=3)
    assert top.best().cost == pytest.approx(plan.best_cost)
    assert [e.cost for e in top.entries] == sorted(e.cost for e in top.entries)


# ---------------------------------------------- heterogeneity + preemption


def _big_small_trace():
    """One big job hogging the cluster, one small job behind it — the
    canonical preemption scenario (distinct class names = two queues)."""
    big = JobClass("batch", HadoopParams(pNumMappers=64, pNumReducers=8,
                                         pSplitSize=64 * MiB),
                   ProfileStats(), CostFactors())
    small = JobClass("adhoc", HadoopParams(pNumMappers=4, pNumReducers=1,
                                           pSplitSize=64 * MiB),
                     ProfileStats(), CostFactors())
    return WorkloadTrace((JobArrival(0, big, 0.0), JobArrival(1, small, 30.0)))


def test_heterogeneous_fleet_orders_latency():
    """More fast silicon at a fixed fleet size strictly helps; num_nodes is
    derived from the class counts."""
    tr = rescale(poisson_trace(CLASSES, 8, seed=1), 0.05)
    cc_het = ClusterConfig(node_classes=(NodeClass(2, 2.0), NodeClass(2, 1.0)))
    assert cc_het.num_nodes == 4
    base = simulate_workload(tr, ClusterConfig(num_nodes=4), CLEAN)
    het = simulate_workload(tr, cc_het, CLEAN)
    fast = simulate_workload(
        tr, ClusterConfig(node_classes=(NodeClass(4, 2.0),)), CLEAN)
    assert fast.p95_latency < het.p95_latency < base.p95_latency
    for r in (base, het, fast):
        assert all(np.isfinite(j.finish) for j in r.jobs)


def test_heterogeneous_homogeneous_speedup_one_is_identical():
    """A one-class fleet at speedup 1.0 is byte-for-byte the homogeneous
    simulation (same RNG draw order, same schedule)."""
    tr = rescale(poisson_trace(CLASSES, 6, seed=2), 0.1)
    a = simulate_workload(tr, ClusterConfig(num_nodes=4), NOISY)
    b = simulate_workload(
        tr, ClusterConfig(node_classes=(NodeClass(4, 1.0),)), NOISY)
    assert a.latencies().tolist() == b.latencies().tolist()
    assert len(a.records) == len(b.records)


def test_preemption_protects_small_job_and_respects_timeout():
    tr = _big_small_trace()
    runs = {
        sched + str(to): simulate_workload(
            tr, ClusterConfig(num_nodes=2, scheduler=sched,
                              preempt_timeout=to), CLEAN)
        for sched, to in [("fifo", 0.0), ("fair", 0.0),
                          ("fair_preempt", 0.0), ("fair_preempt", 20.0)]
    }
    small = {k: r.jobs[1].latency for k, r in runs.items()}
    # preemption beats non-preemptive fair beats FIFO for the queued job
    assert small["fair_preempt0.0"] < small["fair0.0"] < small["fifo0.0"]
    # a longer grace period preempts later (and kills fewer tasks)
    assert small["fair_preempt0.0"] < small["fair_preempt20.0"] < small["fair0.0"]
    assert (runs["fair_preempt0.0"].num_preempted
            >= runs["fair_preempt20.0"].num_preempted > 0)
    assert runs["fifo0.0"].num_preempted == 0
    # work conservation: killed-and-requeued tasks still complete every job
    for r in runs.values():
        assert all(np.isfinite(j.finish) for j in r.jobs)
        assert r.n_unfinished == 0


def test_capacity_scheduler_guarantees_queue_share():
    tr = _big_small_trace()
    fifo = simulate_workload(tr, ClusterConfig(num_nodes=2), CLEAN)
    cap = simulate_workload(
        tr, ClusterConfig(num_nodes=2, scheduler="capacity",
                          preempt_timeout=0.0), CLEAN)
    weighted = simulate_workload(
        tr, ClusterConfig(num_nodes=2, scheduler="capacity",
                          preempt_timeout=0.0,
                          capacities={"adhoc": 3.0, "batch": 1.0}), CLEAN)
    assert cap.jobs[1].latency < fifo.jobs[1].latency
    assert weighted.jobs[1].latency <= cap.jobs[1].latency
    assert cap.num_preempted > 0


@pytest.mark.parametrize("policy,sched", [
    (2.0, "fair_preempt"),
    (3.0, "capacity"),
])
def test_vector_sim_matches_des_preemptive(policy, sched):
    """Kill-and-requeue preemption agrees DES<->wave on the canonical
    big/small scenario (rtol 1e-3) — and preemption actually fires."""
    tr = _big_small_trace()
    cc = ClusterConfig(num_nodes=2, scheduler=sched, preempt_timeout=0.0)
    des = simulate_workload(tr, cc, CLEAN)
    assert des.num_preempted > 0
    out = simulate_batch(scenario_for(tr, cc, 1.0, policy=policy,
                                      queue_frac=[0.5, 0.5]))
    assert out["converged"][0] == 1.0
    des_fin = np.array([j.finish for j in des.jobs])
    np.testing.assert_allclose(out["finish"][0], des_fin, rtol=1e-3)


def test_vector_sim_matches_des_heterogeneous_uncontended():
    """Mixed fleets agree DES<->wave exactly when slots cover the offered
    parallelism (both fill the fast class first; each class's sub-wave
    completes at its own scaled duration)."""
    tr = poisson_trace(CLASSES, 10, rate=1.0, seed=1)
    cc = ClusterConfig(node_classes=(NodeClass(32, 2.0), NodeClass(32, 1.0)))
    des = simulate_workload(rescale(tr, 0.1), cc, CLEAN)
    out = simulate_batch(scenario_for(tr, cc, 0.1))
    assert out["converged"][0] == 1.0
    des_fin = np.array([j.finish for j in des.jobs])
    np.testing.assert_allclose(out["finish"][0], des_fin, rtol=1e-3)
    # and the fast fleet is strictly faster than an all-baseline one
    hom = simulate_batch(scenario_for(
        tr, ClusterConfig(num_nodes=64), 0.1))
    assert out["p95_latency"][0] < hom["p95_latency"][0]


# ------------------------------------------------------- failure-path fixes


def test_unfinished_workload_is_flagged_not_silent():
    """Every node failing leaves jobs unfinished: the result says so
    explicitly (n_unfinished) instead of only an inf latency aggregate."""
    tr = rescale(poisson_trace(CLASSES, 6, seed=3), 0.2)
    dead = simulate_workload(
        tr, ClusterConfig(num_nodes=2),
        SimConfig(speculative_execution=False,
                  node_failures=((1.0, 0), (1.0, 1))))
    assert dead.n_unfinished > 0
    assert np.isinf(dead.mean_latency) and np.isinf(dead.p95_latency)
    ok = simulate_workload(tr, ClusterConfig(num_nodes=2), CLEAN)
    assert ok.n_unfinished == 0 and np.isfinite(ok.mean_latency)


def test_exact_cost_raises_on_unfinished_workload():
    ev = ClusterEvaluator(
        CLASSES, n_jobs=6, n_seeds=1, chunk=8, base_rate=0.2,
        sim=SimConfig(speculative_execution=False,
                      node_failures=((1.0, 0), (1.0, 1))))
    with pytest.raises(UnfinishedWorkloadError, match="never finished"):
        ev.exact_cost({"pNumNodes": 2.0})


def test_slot_utilization_two_segment_hand_computed():
    """2 nodes x 1 map slot, 2 equal maps, node 1 dies halfway through:
    node 0 is busy for the whole (doubled) run and node 1 contributes
    capacity only until its failure — utilization is exactly 1.  The old
    denominator charged the dead node for the full makespan (0.625)."""
    jc = JobClass("maps", HadoopParams(pNumMappers=2, pNumReducers=0,
                                       pSplitSize=64 * MiB),
                  ProfileStats(), CostFactors())
    mc, _, _ = task_costs(jc, num_nodes=2)
    tr = WorkloadTrace((JobArrival(0, jc, 0.0),))
    r = simulate_workload(
        tr,
        ClusterConfig(num_nodes=2, map_slots_per_node=1,
                      reduce_slots_per_node=0),
        SimConfig(speculative_execution=False,
                  node_failures=((mc / 2, 1),)))
    assert r.num_failure_reruns == 1
    assert r.makespan == pytest.approx(2 * mc)
    assert sum(r.node_busy_s) == pytest.approx(2.5 * mc)
    assert r.slot_utilization == pytest.approx(1.0)


def test_failure_runs_utilization_bounded_and_finite():
    """Noisy failure runs: finite costs or an explicit n_unfinished, and a
    time-integrated utilization that stays physical (<= 1)."""
    for seed in range(4):
        tr = rescale(poisson_trace(CLASSES, 8, seed=seed), 0.1)
        r = simulate_workload(
            tr, ClusterConfig(num_nodes=4),
            SimConfig(seed=seed, straggler_prob=0.2, task_time_jitter=0.3,
                      node_failures=((5.0, seed % 4), (9.0, (seed + 1) % 4))))
        assert 0.0 <= r.slot_utilization <= 1.0 + 1e-9
        if r.n_unfinished == 0:
            assert all(np.isfinite(j.finish) for j in r.jobs)
            assert np.isfinite(r.mean_latency)
        else:
            assert np.isinf(r.mean_latency)


@pytest.mark.parametrize("sched", ["fifo", "fair"])
def test_map_output_resurrection_completes(sched):
    """A node failure after the maps finish resurrects map work while the
    reduces are mid-flight: the stalled reduces must wait for the re-run
    outputs and then complete (the reduce_durs bookkeeping survives the
    kill/stall/resume cycle under both policies)."""
    jc = JobClass("one", HadoopParams(pNumMappers=16, pNumReducers=4,
                                      pSplitSize=64 * MiB),
                  ProfileStats(), CostFactors())
    tr = WorkloadTrace((JobArrival(0, jc, 0.0), JobArrival(1, jc, 1.0)))
    cc = ClusterConfig(num_nodes=4, scheduler=sched)
    base = simulate_workload(tr, cc, CLEAN)
    mf = max(j.map_finish for j in base.jobs)
    fin = max(j.finish for j in base.jobs)
    ftime = mf + 0.25 * (fin - mf)         # reduces running, maps done
    failed = simulate_workload(
        tr, cc, SimConfig(speculative_execution=False,
                          node_failures=((ftime, 0),)))
    assert failed.num_failure_reruns > 0
    # map work was resurrected after the original map fleet finished ...
    assert any(rec.kind == "map" and rec.start >= ftime and not rec.killed
               for rec in failed.records)
    # ... and every job still completed, later than the clean run
    assert failed.n_unfinished == 0
    assert all(np.isfinite(j.finish) for j in failed.jobs)
    assert max(j.finish for j in failed.jobs) > fin


def test_task_costs_memoized_per_class(monkeypatch):
    """Packing a big trace does ~one job_model call per class, not one per
    arrival (the old pack_trace re-evaluated the model 2x per job)."""
    from repro.cluster import workload as wl

    calls = {"n": 0}
    real = wl.job_model

    def counting(*a, **k):
        calls["n"] += 1
        return real(*a, **k)

    monkeypatch.setattr(wl, "job_model", counting)
    wl._job_model_cached.cache_clear()
    tr = poisson_trace(CLASSES, 200, rate=1.0, seed=7)
    pack_trace(tr)
    assert calls["n"] <= len(CLASSES)
    wl._job_model_cached.cache_clear()


# --------------------------------------------------- planner, new axes


def test_evaluator_heterogeneous_axes(evaluator):
    res = evaluator.evaluate({
        "pNumFastNodes": np.array([0.0, 2.0, 4.0]), "fastSpeedup": 2.0})
    assert res.outputs["valid"].all()
    # more fast nodes at a fixed fleet size never hurts the tail
    assert np.all(np.diff(res.total_cost) <= 1e-3)
    # the cross-axis predicate: a fast class larger than the fleet is invalid
    bad = evaluator.evaluate({"pNumFastNodes": np.array([8.0, 1.0])})
    assert bad.outputs["valid"][0] == 0.0 and np.isinf(bad.total_cost[0])
    assert bad.outputs["valid"][1] == 1.0
    assert evaluator.exact_cost({"pNumFastNodes": 8.0}) == np.inf
    # vector vs DES on a mixed fleet (light load: wave structure holds)
    vec = float(evaluator.evaluate(
        {"pNumFastNodes": np.array([2.0]), "fastSpeedup": 2.0}).total_cost[0])
    des = evaluator.exact_cost({"pNumFastNodes": 2.0, "fastSpeedup": 2.0})
    assert vec == pytest.approx(des, rel=0.1)


def test_evaluator_policy_axes_searchable(evaluator):
    space = {"schedPolicy": [0.0, 1.0, 2.0, 3.0], "pNumNodes": [2.0, 4.0]}
    plan = grid_search_ev(evaluator, space)
    assert np.isfinite(plan.best_cost) and plan.evaluations == 8
    top = search_topk(evaluator, space, k=3)
    assert top.best().cost == pytest.approx(plan.best_cost)


def test_fair_base_seeds_sched_policy():
    """A fair-scheduler base seeds schedPolicy = 1, as every base seeds its
    own code, and the axis alone still toggles FIFO vs fair on it."""
    ev = ClusterEvaluator(CLASSES, n_jobs=8, n_seeds=1, chunk=8,
                          base=ClusterConfig(num_nodes=2, scheduler="fair"),
                          base_rate=0.2)
    fifo = ev.exact_cost({"schedPolicy": 0.0})
    fair = ev.exact_cost({"schedPolicy": 1.0})
    assert fifo != fair
    assert fair == ev.exact_cost({})                  # base default is fair


def test_inexpressible_base_fleet_rejected():
    """The axis space models (fast + unit baseline); richer base fleets must
    fail loudly instead of being silently projected onto the wrong cluster."""
    three = ClusterConfig(node_classes=(
        NodeClass(2, 2.0), NodeClass(2, 1.5), NodeClass(2, 1.0)))
    with pytest.raises(ValueError, match="not expressible"):
        ClusterEvaluator(CLASSES, n_jobs=4, n_seeds=1, base=three)
    slow_base = ClusterConfig(node_classes=(NodeClass(2, 2.0),
                                            NodeClass(2, 0.5)))
    with pytest.raises(ValueError, match="not expressible"):
        ClusterEvaluator(CLASSES, n_jobs=4, n_seeds=1, base=slow_base)


def test_exact_fallback_skips_unfinishable_candidates(evaluator, monkeypatch):
    """One unfinishable candidate in the exact escape hatch must not abort a
    completed search: top-k catches ExactCostUnavailable and keeps ranking."""
    monkeypatch.setattr(
        type(evaluator), "exact_cost",
        lambda self, a: (_ for _ in ()).throw(
            UnfinishedWorkloadError("jobs never finished")))
    space = {"pNumNodes": [0.0, 4.0, 8.0]}       # row 0 invalid -> fallback
    top = search_topk(evaluator, space, k=3, exact_fallback=True)
    assert len(top.entries) == 2                  # the two valid rows ranked
    assert np.isfinite(top.best().cost)


def test_capacity_default_queue_frac_matches_equal_shares():
    """simulate_batch without queue_frac defaults to equal guarantees over
    the queues present — the DES's default — not a 100% queue-0 guarantee."""
    tr = poisson_trace(CLASSES, 8, rate=1.0, seed=4)
    cc = ClusterConfig(num_nodes=2, scheduler="capacity", preempt_timeout=0.0)
    n_q = len({a.klass.name for a in tr.arrivals})
    explicit = simulate_batch(scenario_for(tr, cc, 0.2, policy=3.0,
                                           queue_frac=[1.0 / n_q] * n_q))
    defaulted = simulate_batch(scenario_for(tr, cc, 0.2, policy=3.0))
    np.testing.assert_array_equal(explicit["finish"], defaulted["finish"])


def test_whatif_service_bit_for_bit(evaluator):
    vals = np.asarray([0.02, 0.05, 0.1], np.float32)
    with WhatIfService(evaluator) as svc:
        swept = svc.sweep("arrivalRate", vals).result()
        probe = svc.probe({"pNumNodes": 8.0}).result()
    seq = evaluator.evaluate({"arrivalRate": vals})
    assert np.array_equal(swept.total_cost, seq.total_cost)
    for k in seq.outputs:
        assert np.array_equal(swept.outputs[k], seq.outputs[k]), k
    assert probe.total_cost.shape == (1,) and np.isfinite(probe.total_cost[0])
