"""The Hive TPC-H planner cell end to end through the harness on the CPU, cut
to a size the CPU runs in seconds: it agrees with the DAG reference, its
traced run reads the program's counters, and the control and the planted
faults (a join gated on its first parent only, half of each batch left out,
rows cut short by the rollout's step cap) are not correct."""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import copy  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import time  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402
import pytest  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402

from perfbench.harness.bench import Bench  # noqa: E402
from perfbench.harness.controls import control_kw  # noqa: E402
from perfbench.harness.runner import run_cell  # noqa: E402

CELL = "planner-hive-dag-racks"


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    """The cell at scale factor 1, with two streams of four queries (two
    with fan-in, one of them the three-parent Q21) and 288 small racked
    clusters: two chunks, so one block is compared whole and one sampled."""
    d = tmp_path_factory.mktemp("bench")
    base = Bench(ROOT)
    spec = copy.deepcopy(base.spec)
    cfg = base.config("tpch-sf1000-hive")
    for t in cfg["tables"].values():
        t["rows"] = t["rows"] / 1000
    cfg["queries"] = {q: cfg["queries"][q] for q in ("q02", "q06", "q17", "q21")}
    cfg["streams"] = 2
    cfg["grid"] = {"pNumNodes": [2.0, 4.0, 8.0], "pMaxMapsPerNode": [1.0, 2.0],
                   "pMaxRedPerNode": [1.0, 2.0], "pReduceSlowstart": [0.05, 0.8],
                   "schedPolicy": [0.0, 1.0], "pNumRacks": [1.0, 2.0],
                   "oversubscription": [1.0, 4.0, 10.0], "crossRackBw": [1.0]}
    for c in spec["configs"]:
        if c["name"] == "tpch-sf1000-hive":
            (d / "cfg.json").write_text(json.dumps(cfg))
            c["file"] = str(d / "cfg.json")
    traffic = base.traffic("planner-dag-grid")
    traffic["sample_rows"] = 4
    (d / "planner-dag-grid.json").write_text(json.dumps(traffic))
    return Bench(ROOT, spec=spec, traffic_dir=d)


def _run(bench, seed=2**31 + 23, trace=False, **kw):
    with jax.enable_x64(False):
        return run_cell(bench, CELL, seed=seed, seconds=0.5, trace=trace,
                        devs=jax.devices(), t_start=time.perf_counter(), **kw)


def test_the_cut_trace_keeps_fan_in_and_racks(bench):
    from perfbench.harness.hive_dag import HiveStreams

    h = HiveStreams(bench.config("tpch-sf1000-hive"), 5)
    ev = h.program_evaluator()
    assert ev.cost_key == "w_makespan"
    assert ev._cols["dep"].shape == (2, 2 * 16, 3)          # Q21's join: three parents
    parents = (ev._cols["dep"] >= 0).sum(-1)
    # only the first query of each stream has stages that wait on nothing
    assert parents.max() == 3 and ((parents == 0).sum(-1) >= 2).all()
    assert max(bench.config("tpch-sf1000-hive")["grid"]["pNumRacks"]) > 1


@pytest.mark.parametrize("seed", [2**31 + 23, 97])
def test_cell_runs_correct_against_the_dag_reference(bench, seed):
    out = _run(bench, seed=seed)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"scenarios_per_s", "setup_s"}
    assert out["metrics"]["scenarios_per_s"]["value"] > 0
    assert out["checks"]["makespan_gap_ulp"]["limit"] == 6
    assert out["checks"]["uncontended_share"]["value"] < 0.75


def test_traced_run_reads_the_program_counters(bench, tmp_path, monkeypatch):
    import perfbench.harness.runner as runner

    monkeypatch.setattr(runner, "OUT_DIR", tmp_path)
    out = _run(bench, trace=True)
    assert out["correct"]
    assert out["attempted"] >= 1
    m = out["metrics"]
    assert {"rollout.lane_use", "planner.build_ms", "rollout.release_step_share"} <= set(m)
    assert 0 < m["rollout.release_step_share"]["value"] < m["rollout.lane_use"]["value"] <= 100


def test_control_in_the_next_precision_down_is_not_correct(bench):
    out = _run(bench, **control_kw("planner_dag"))
    assert not out["correct"]
    assert out["checks"]["makespan_gap_ulp"]["value"] > 6 \
        or out["checks"]["valid_mismatch"]["value"] > 0


class _FirstParentOnly:
    """The program's evaluator on the same traces with each job gated on its
    first parent only."""

    def __new__(cls, inner):
        from repro.cluster import ClusterEvaluator
        from repro.cluster.workload import JobArrival, WorkloadTrace

        traces = [WorkloadTrace(tuple(JobArrival(a.job_id, a.klass, a.submit_time, a.deps[:1])
                                      for a in t.arrivals)) for t in inner.traces]
        return ClusterEvaluator(inner.classes, traces=traces, objective="makespan")


class _HalfBatch:
    def __init__(self, inner):
        self._inner = inner

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def chunk_topk(self, overrides, k):
        n = len(next(iter(overrides.values())))
        return self._inner.chunk_topk({c: v[: n // 2] for c, v in overrides.items()}, k)


@pytest.mark.parametrize("plant", [_FirstParentOnly, _HalfBatch])
def test_a_planted_fault_is_not_correct(bench, plant):
    out = _run(bench, plant=plant)
    assert not out["correct"], out["checks"]


def test_a_rollout_truncated_by_its_step_cap_is_not_correct(bench, monkeypatch):
    # the program's rows stopped at 64 steps, fewer than the cut trace's
    # slowest rows need, come back invalid; the reference runs every row
    # to its end, so the winner's block counts more valid rows than the
    # device
    import repro.cluster.evaluator as program

    monkeypatch.setattr(program, "estimate_steps", lambda scen: 64)
    out = _run(bench)
    assert not out["correct"]
    assert out["checks"]["valid_count_gap"]["value"] > 0


def test_reference_releases_a_join_at_its_latest_parent():
    from perfbench.reference import wave_dag_ref

    # three one-map jobs of 1, 5 and 3 s feed a join listed after them
    sc = {"arrival": np.zeros((1, 4)), "n_maps": np.ones((1, 4)), "n_reds": np.zeros((1, 4)),
          "map_dur": np.asarray([[1.0, 5.0, 3.0, 2.0]]), "shuffle": np.zeros((1, 4)),
          "red_work": np.zeros((1, 4)), "map_slots": np.asarray([8.0]),
          "red_slots": np.asarray([8.0]), "fair": np.zeros(1), "slowstart": np.ones(1),
          "racks": np.ones(1), "cross_bw": np.full(1, np.inf), "oversub": np.ones(1),
          "dep": np.asarray([[-1, -1, -1], [-1, -1, -1], [-1, -1, -1], [0, 1, 2]])}
    fin, conv, _ = wave_dag_ref.simulate(**sc)
    assert conv[0] and fin[0].tolist() == [1.0, 5.0, 3.0, 7.0]


def test_reference_incast_bandwidth_is_the_count_approximation():
    from perfbench.reference import wave_dag_ref

    bw = wave_dag_ref.incast_bandwidth(np.asarray([1.0, 4.0, 4.0, 4.0]),
                                       np.asarray([40.0, 40.0, 40.0, np.inf]),
                                       np.asarray([1.0, 10.0, 1.0, 1.0]),
                                       np.asarray([500.0, 80.0, 2.0, 80.0]), np.float64)
    # one rack never contends; 4 racks at 4 flows/unit: 4 / (0.75 * 20)
    assert bw.tolist() == [1.0, 4.0 / 15.0, 1.0, 1.0]


def test_reference_stops_a_row_whose_clock_stands_still():
    import ml_dtypes

    from perfbench.reference import wave_dag_ref

    # 600 maps of 0.7 s one after another on one slot: past 256 s bfloat16's
    # spacing is 2 s, so every task end rounds to the present and the clock
    # stops; float64 steps through to the end
    sc = {"arrival": np.zeros((1, 1)), "n_maps": np.full((1, 1), 600.0),
          "n_reds": np.zeros((1, 1)), "map_dur": np.full((1, 1), 0.7),
          "shuffle": np.zeros((1, 1)), "red_work": np.zeros((1, 1)),
          "map_slots": np.ones(1), "red_slots": np.ones(1), "fair": np.zeros(1),
          "slowstart": np.ones(1), "racks": np.ones(1), "cross_bw": np.full(1, np.inf),
          "oversub": np.ones(1), "dep": np.full((1, 1), -1)}
    fin, conv, _ = wave_dag_ref.simulate(**sc)
    assert conv[0] and fin[0, 0] == pytest.approx(420.0)
    fin, conv, _ = wave_dag_ref.simulate(**sc, dtype=ml_dtypes.bfloat16)
    assert not conv[0] and np.isinf(fin[0, 0])
