"""Topology-aware network model + DAG workloads.

Three contracts pinned here:

* **Seed regression** — :func:`repro.cluster.network.per_reducer_shuffle`
  is bit-for-bit the ``netCost / pNumReducers`` term the seed computed
  inline (single-job simulator and workload task costs), and
  ``Topology.flat()`` reproduces the no-topology DES record-for-record
  under every scheduler with noise on.
* **Contention semantics** — max-min fair shares by progressive filling,
  ``effective_bandwidth`` differentiable and NaN-free at every boundary,
  contended topologies strictly slower, uncontended ones bit-identical.
* **DAG invariant** — ``DagReport.critical_path_s <= makespan_s`` always,
  with equality on serial (width-1) chains, across every
  ``mapreduce.JOBS`` profile, both edge kinds and all four schedulers.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cluster import (
    ClusterConfig,
    SimConfig,
    StageDag,
    StageEdge,
    Topology,
    dag_from_templates,
    dag_report,
    dag_trace,
    default_job_classes,
    effective_bandwidth,
    per_reducer_shuffle,
    simulate_workload,
)
from repro.cluster.network import flow_rates, max_min_rates
from repro.cluster.vector_sim import pack_trace, simulate_batch
from repro.cluster.workload import (
    JobArrival,
    JobClass,
    WorkloadTrace,
    _job_model_cached,
    stage_output_bytes,
    task_costs,
)
from repro.mapreduce.jobs import JOBS

CLASSES = default_job_classes()
BY_NAME = {jc.name: jc for jc in CLASSES}

NOISY = SimConfig(seed=11, task_time_jitter=0.2, straggler_prob=0.1)
SCHEDULERS = ("fifo", "fair", "fair_preempt", "capacity")


def _record_tuples(res):
    return [(r.kind, r.index, r.job_id, r.node, r.start, r.end,
             r.speculative, r.killed) for r in res.records]


# ---------------------------------------------------------------------------
# seed regression: the hoisted shuffle term + the flat topology
# ---------------------------------------------------------------------------


def test_per_reducer_shuffle_pins_seed_term():
    # the exact expression the seed computed inline at both call sites
    for jc in CLASSES:
        jm = _job_model_cached(jc.params, jc.stats, jc.costs)
        expected = jm.netCost / jc.params.pNumReducers
        assert task_costs(jc)[2] == expected                    # bit-for-bit
        assert per_reducer_shuffle(jm.netCost, jc.params.pNumReducers) \
            == expected
    assert per_reducer_shuffle(123.0, 0) == 0.0                 # map-only


@pytest.mark.parametrize("sched", SCHEDULERS)
def test_flat_topology_bit_for_bit(sched):
    from repro.cluster.workload import poisson_trace, rescale

    tr = rescale(poisson_trace(CLASSES, 10, seed=5), 0.2)
    base = ClusterConfig(num_nodes=6, scheduler=sched)
    ref = simulate_workload(tr, base, NOISY)
    for topo in (Topology.flat(), Topology(num_racks=1),
                 Topology(num_racks=3)):     # racks with inf bw stay flat
        got = simulate_workload(
            tr, ClusterConfig(num_nodes=6, scheduler=sched, topology=topo),
            NOISY)
        assert _record_tuples(got) == _record_tuples(ref)
        assert got.makespan == ref.makespan


def test_contended_topology_strictly_slower_uncontended_identical():
    from repro.cluster.workload import poisson_trace, rescale

    tr = rescale(poisson_trace(CLASSES, 8, seed=2), 0.3)
    flat = simulate_workload(tr, ClusterConfig(num_nodes=8), SimConfig(seed=0))
    tight = Topology(num_racks=4, cross_rack_bw=0.5, oversub=2.0)
    slow = simulate_workload(
        tr, ClusterConfig(num_nodes=8, topology=tight), SimConfig(seed=0))
    assert slow.makespan > flat.makespan
    # non-flat but huge uplink: every fair share caps at the nominal rate
    roomy = Topology(num_racks=2, cross_rack_bw=1e9)
    same = simulate_workload(
        tr, ClusterConfig(num_nodes=8, topology=roomy), SimConfig(seed=0))
    assert _record_tuples(same) == _record_tuples(flat)


# ---------------------------------------------------------------------------
# max-min fair sharing + the differentiable approximation
# ---------------------------------------------------------------------------


def test_max_min_progressive_filling_hand_cases():
    # one saturated link shared by two flows -> 0.5 each; a third flow on
    # an uncontended link keeps the nominal rate
    rates = max_min_rates(
        [{"a": 1.0}, {"a": 1.0}, {"b": 1.0}], {"a": 1.0, "b": 5.0})
    assert rates == pytest.approx([0.5, 0.5, 1.0])
    # progressive filling: the flow leaving the saturated link is frozen at
    # the saturation level, the other keeps rising to its own bottleneck
    rates = max_min_rates(
        [{"a": 1.0, "b": 1.0}, {"b": 1.0}], {"a": 0.4, "b": 2.0})
    assert rates == pytest.approx([0.4, 1.0])
    # infinite-capacity links never constrain; empty usage = nominal rate
    assert max_min_rates([{"x": 2.0}, {}], {"x": float("inf")}) == [1.0, 1.0]


def test_flow_rates_incast_shares_rack_uplink():
    topo = Topology(num_racks=2, cross_rack_bw=1.0, oversub=2.0)
    # four concurrent reducers on rack 0's nodes: rack capacity 0.5 split
    # by cross_frac weight 0.5 each -> 0.25 apiece... (4 flows, weight 1/2)
    rates = flow_rates(topo, [0, 2, 4, 6], num_nodes=8)
    assert rates == pytest.approx([0.25] * 4)
    # a single flow is uncontended but still uplink-bounded below nominal
    assert flow_rates(topo, [0], num_nodes=8) == pytest.approx([1.0])


def test_effective_bandwidth_values_and_grads():
    fdt = jnp.result_type(float)
    one = jnp.asarray(1.0, fdt)
    # flat spellings: one rack, or an infinite uplink
    assert float(effective_bandwidth(one, jnp.asarray(jnp.inf, fdt),
                                     one, 8.0 * one)) == 1.0
    assert float(effective_bandwidth(4.0 * one, jnp.asarray(jnp.inf, fdt),
                                     one, 8.0 * one)) == 1.0
    # 4 racks, capacity 0.5/rack, 8 flows: 2/rack, demand 0.75*2 = 1.5
    got = effective_bandwidth(4.0 * one, one, 2.0 * one, 8.0 * one)
    assert float(got) == pytest.approx(0.5 / 1.5)
    # never exceeds nominal
    assert float(effective_bandwidth(2.0 * one, 100.0 * one, one, one)) == 1.0
    # gradients finite everywhere, including the flat boundary (the
    # double-where contract every model path relies on)
    g = jax.grad(lambda x: effective_bandwidth(4.0 * one, x, 2.0 * one,
                                               8.0 * one))(one)
    assert jnp.isfinite(g) and float(g) > 0
    g0 = jax.grad(lambda r: effective_bandwidth(r, one, one, 8.0 * one))(one)
    assert jnp.isfinite(g0)


def test_topology_validation():
    with pytest.raises(ValueError):
        Topology(num_racks=0)
    with pytest.raises(ValueError):
        Topology(num_racks=2, cross_rack_bw=0.0)
    with pytest.raises(ValueError):
        Topology(num_racks=2, oversub=0.5)
    assert Topology.flat().is_flat
    assert not Topology(num_racks=2, cross_rack_bw=1.0).is_flat


def test_job_model_topology_hook_double_where():
    from repro.core.hadoop.model import job_model_jnp, pack_config
    from repro.core.hadoop.params import CostFactors, HadoopParams, ProfileStats

    fdt = jnp.result_type(float)
    cfg = pack_config(HadoopParams(pNumMappers=16, pNumReducers=8,
                                   pNumNodes=8),
                      ProfileStats(), CostFactors())
    flat = job_model_jnp(dict(cfg))["j_totalCost"]
    # racks=1 hook present == hook absent, bit-for-bit
    same = job_model_jnp(dict(cfg, pNumRacks=jnp.asarray(1.0, fdt)))
    assert float(same["j_totalCost"]) == float(flat)
    topo = dict(cfg, pNumRacks=jnp.asarray(4.0, fdt),
                crossRackBw=jnp.asarray(0.5, fdt),
                oversubscription=jnp.asarray(2.0, fdt))
    assert float(job_model_jnp(topo)["j_totalCost"]) > float(flat)
    # the searched gradient is finite and points the right way (more
    # uplink -> cheaper), including at the racks=1 boundary
    g = jax.grad(lambda x: job_model_jnp(
        {**topo, "crossRackBw": x})["j_totalCost"])(jnp.asarray(0.5, fdt))
    assert jnp.isfinite(g) and float(g) < 0
    g1 = jax.grad(lambda r: job_model_jnp(
        {**topo, "pNumRacks": r})["j_totalCost"])(jnp.asarray(1.0, fdt))
    assert jnp.isfinite(g1)


# ---------------------------------------------------------------------------
# DES <-> wave agreement under contention
# ---------------------------------------------------------------------------


def _wave_one(trace, *, nodes, topo=None):
    cols = pack_trace(trace)
    n = len(trace.arrivals)
    frac = (nodes - 1.0) / nodes
    scen = {k: v[None] for k, v in cols.items()}
    scen["shuffle"] = scen["shuffle"] * frac
    scen["map_slots"] = np.asarray([[nodes * 2.0]])
    scen["red_slots"] = np.asarray([[nodes * 2.0]])
    scen["policy"] = np.zeros(1)
    scen["slowstart"] = np.full(1, 0.05)
    scen["queue_frac"] = np.ones((1, 1))
    scen["queue"] = np.zeros((1, n))
    if topo is not None:
        scen["topo_racks"] = np.full(1, float(topo.num_racks))
        scen["topo_cross_bw"] = np.full(1, topo.cross_rack_bw)
        scen["topo_oversub"] = np.full(1, topo.oversub)
    return simulate_batch(scen, n_steps=256)


def test_wave_matches_des_single_incast_job():
    # one sort job saturating the uplink: the wave count-approximation and
    # the DES fair-share integration see the identical contention state
    tr = WorkloadTrace((JobArrival(0, BY_NAME["sort"], 0.0),))
    topo = Topology(num_racks=4, cross_rack_bw=0.5, oversub=2.0)
    des = simulate_workload(
        tr, ClusterConfig(num_nodes=8, topology=topo), SimConfig(seed=0))
    out = _wave_one(tr, nodes=8, topo=topo)
    assert out["converged"][0] == 1.0
    np.testing.assert_allclose(out["makespan"][0], des.makespan, rtol=1e-3)


def test_wave_flat_unchanged_by_topology_columns():
    from repro.cluster.workload import poisson_trace

    tr = poisson_trace(CLASSES, 6, seed=4)
    base = _wave_one(tr, nodes=8)
    flat = _wave_one(tr, nodes=8, topo=Topology(num_racks=1))
    np.testing.assert_array_equal(base["latency"], flat["latency"])


# ---------------------------------------------------------------------------
# DAG workloads
# ---------------------------------------------------------------------------


def test_dag_validation_errors():
    wc = BY_NAME["wordcount"]
    with pytest.raises(ValueError, match="cycle"):
        StageDag("c", (wc, wc), (StageEdge(0, 1), StageEdge(1, 0)))
    with pytest.raises(ValueError, match="self-edge"):
        StageDag("s", (wc,), (StageEdge(0, 0),))
    with pytest.raises(ValueError, match="out of range"):
        StageDag("r", (wc,), (StageEdge(0, 3),))
    with pytest.raises(ValueError, match="duplicate"):
        StageDag("d", (wc, wc), (StageEdge(0, 1), StageEdge(0, 1)))
    with pytest.raises(ValueError, match="edge kind"):
        StageDag("k", (wc, wc), (StageEdge(0, 1, "sloppy"),))


def test_dag_dataflow_sizes_downstream_stages():
    # the child's mapper count comes from the parent's Table-1 output
    # bytes, not from the template
    dag = dag_from_templates(
        "two", [BY_NAME["sort"], BY_NAME["sort"]], [(0, 1)])
    parent = dag.stages[0]
    child = dag.stages[1]
    expect = max(1, int(np.ceil(
        stage_output_bytes(parent) / child.params.pSplitSize)))
    assert child.params.pNumMappers == expect
    assert parent.params.pNumMappers == BY_NAME["sort"].params.pNumMappers


def test_dag_releases_at_barrier_and_slowstart():
    dag = dag_from_templates(
        "chain", [BY_NAME["wordcount"], BY_NAME["sort"], BY_NAME["filter"]],
        [(0, 1, "barrier"), (1, 2, "slowstart")])
    tr = dag_trace(dag)
    res = simulate_workload(tr, ClusterConfig(num_nodes=8), SimConfig(seed=1))
    js = {j.job_id: j for j in res.jobs}
    assert js[1].submit_time == js[0].finish
    assert js[2].submit_time == js[1].map_finish
    assert js[2].submit_time < js[1].finish


def _wave_vs_des(trace, nodes):
    """Per-job finish times of the wave model and of the DES, in the
    trace's arrival order, on one contention-free cluster."""
    des = simulate_workload(trace, ClusterConfig(num_nodes=nodes),
                            SimConfig(seed=0))
    by_id = {js.job_id: js for js in des.jobs}
    out = _wave_one(trace, nodes=nodes)
    assert out["converged"][0] == 1.0 and des.n_unfinished == 0
    want = [by_id[a.job_id].finish for a in trace.arrivals]
    return out["finish"][0], np.asarray(want)


_FAN_IN = {
    # (stages, edges): a diamond, a 3-parent join, and both edge kinds
    # feeding one join
    "diamond": (("wordcount", "sort", "filter", "aggregate"),
                [(0, 1), (0, 2), (1, 3), (2, 3)]),
    "three_parent_join": (("sort", "wordcount", "filter", "aggregate"),
                          [(0, 3), (1, 3), (2, 3)]),
    "mixed_edges": (("wordcount", "sort", "filter", "aggregate"),
                    [(0, 1, "slowstart"), (0, 2), (1, 3, "slowstart"),
                     (2, 3)]),
}


@pytest.mark.parametrize("name", sorted(_FAN_IN))
def test_wave_fan_in_dag_tracks_des(name):
    # two interleaved instances on a cluster with a slot for every task:
    # no wave ever waits, so the wave model is exact per job
    stages, edges = _FAN_IN[name]
    dag = dag_from_templates(name, [BY_NAME[n] for n in stages], edges)
    tr = dag_trace(dag, n_instances=2, inter_arrival=3.0)
    assert pack_trace(tr)["dep"].shape == (
        tr.n_jobs, max(len(dag.parents_of(i)) for i in range(len(stages))))
    got, want = _wave_vs_des(tr, nodes=256)
    np.testing.assert_allclose(got, want, rtol=1e-3)


@pytest.mark.parametrize("late", [1, 2])
def test_wave_join_waits_for_its_latest_parent(late):
    # the join's parents finish at different times; the latest one is
    # listed second or third, so gating on the first parent releases the
    # join too early
    sort, wc = BY_NAME["sort"], BY_NAME["wordcount"]
    big = JobClass(name="big", stats=sort.stats, costs=sort.costs,
                   params=sort.params.replace(pNumMappers=64))
    parents = [wc, wc, wc]
    parents[late] = big
    deps = tuple((i, "barrier") for i in range(3))
    tr = WorkloadTrace(tuple(JobArrival(i, jc, 0.0)
                             for i, jc in enumerate(parents))
                       + (JobArrival(3, sort, 0.0, deps=deps),))
    assert pack_trace(tr)["dep"].tolist() == [[-1] * 3] * 3 + [[0, 1, 2]]
    out = _wave_one(tr, nodes=64)
    fin = out["finish"][0]
    release = fin[3] - out["latency"][0][3]
    assert fin[late] == fin[:3].max() > fin[:3].min()
    np.testing.assert_allclose(release, fin[late], rtol=1e-6)
    got, want = _wave_vs_des(tr, nodes=64)
    np.testing.assert_allclose(got, want, rtol=1e-3)


def test_release_steps_count_one_step_per_release():
    from repro.obs import observe

    dag = dag_from_templates(
        "chain3", [BY_NAME["sort"]] * 3, [(0, 1), (1, 2, "slowstart")])
    tr = dag_trace(dag)
    with observe() as ob:
        out = _wave_one(tr, nodes=8)
    assert out["converged"][0] == 1.0
    reg = ob.registry
    assert reg.counter("vector_sim.release_steps").value == 2
    assert reg.counter("vector_sim.lane_steps").value > 2
    # a batch with no edges keeps the lean kernel and counts none
    with observe() as ob:
        _wave_one(WorkloadTrace((JobArrival(0, BY_NAME["sort"], 0.0),)),
                  nodes=8)
    assert ob.registry.counter("vector_sim.release_steps").value == 0


def test_cluster_evaluator_searches_fan_in_dags_by_makespan():
    from repro.cluster import ClusterEvaluator

    stages, edges = _FAN_IN["three_parent_join"]
    dag = dag_from_templates("join", [BY_NAME[n] for n in stages], edges)
    tr = dag_trace(dag, n_instances=2, inter_arrival=3.0)
    ev = ClusterEvaluator(traces=[tr], objective="makespan", chunk=4)
    assert ev.cost_key == "w_makespan"
    res = ev.evaluate({"pNumNodes": np.asarray([256.0, 4.0])})
    assert res.outputs["valid"].tolist() == [1.0, 1.0]
    # contention-free row: the DES's makespan, first arrival to last finish
    exact = ev.exact_cost({"pNumNodes": 256.0})
    np.testing.assert_allclose(res.outputs["w_makespan"][0], exact, rtol=1e-3)
    assert res.outputs["w_makespan"][1] > res.outputs["w_makespan"][0]


def test_wave_dag_chain_tracks_des():
    dag = dag_from_templates(
        "chain", [BY_NAME["sort"], BY_NAME["sort"]], [(0, 1, "barrier")])
    tr = dag_trace(dag)
    des = simulate_workload(tr, ClusterConfig(num_nodes=8), SimConfig(seed=0))
    out = _wave_one(tr, nodes=8)
    assert out["converged"][0] == 1.0
    np.testing.assert_allclose(out["makespan"][0], des.makespan, rtol=1e-3)


@pytest.mark.parametrize("profile", sorted(JOBS))
@pytest.mark.parametrize("kind", ["barrier", "slowstart"])
def test_critical_path_equals_makespan_on_serial_chains(profile, kind):
    jc = BY_NAME[profile]
    dag = dag_from_templates(f"{profile}-{kind}", [jc, jc, jc],
                             [(0, 1, kind), (1, 2, kind)])
    assert dag.is_serial
    tr = dag_trace(dag)
    res = simulate_workload(tr, ClusterConfig(num_nodes=8), SimConfig(seed=3))
    rep = dag_report(tr, res)
    cp, mk = float(rep.critical_path_s), float(rep.makespan_s)
    assert cp == pytest.approx(mk, abs=1e-9)
    assert float(rep.slack_s) == pytest.approx(0.0, abs=1e-9)


@pytest.mark.parametrize("sched", SCHEDULERS)
def test_critical_path_never_exceeds_makespan(sched):
    # a diamond per profile pair, two interleaved instances, noisy DES,
    # small cluster so stages really queue — the adversarial setting for
    # the invariant
    stages = [BY_NAME[n] for n in ("wordcount", "sort", "filter", "aggregate")]
    dag = dag_from_templates(
        "diamond", stages,
        [(0, 1), (0, 2, "slowstart"), (1, 3), (2, 3, "slowstart")])
    tr = dag_trace(dag, n_instances=2, inter_arrival=3.0)
    res = simulate_workload(
        tr, ClusterConfig(num_nodes=3, map_slots_per_node=1,
                          reduce_slots_per_node=1, scheduler=sched),
        NOISY)
    rep = dag_report(tr, res)
    assert float(rep.critical_path_s) <= float(rep.makespan_s) + 1e-9
    assert float(rep.slack_s) >= -1e-9
    # the report is a registered pytree of arrays (spec contract)
    leaves = jax.tree_util.tree_leaves(rep)
    assert len(leaves) == 6
    assert rep.stage_runtime_s.shape == (tr.n_jobs,)


def test_dag_report_rejects_cyclic_edges():
    from repro.spec import DagReport

    with pytest.raises(ValueError, match="cycle"):
        DagReport.from_times([0.0, 0.0], [0.0, 0.0], [1.0, 1.0], [2.0, 2.0],
                             [(0, 1, "barrier"), (1, 0, "barrier")])
