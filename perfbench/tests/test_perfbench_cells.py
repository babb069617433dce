"""Every cell end to end through the harness on the CPU, at a tiny size:
the result line's schema, a cell added by files alone, the controls and the
faults each comparison has to catch."""

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

GRID, SERVICE, PLANNER = ("whatif-terasort-grid", "whatif-terasort-service",
                          "planner-fb2009-hour")
LOAD = {GRID: "grid_topk", SERVICE: "service_open", PLANNER: "planner_grid"}


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    """The benchmark at a size the CPU runs in seconds: fewer knob values,
    fewer and smaller queries, fewer jobs and clusters."""
    d = tmp_path_factory.mktemp("bench")
    base = Bench(ROOT)
    spec = copy.deepcopy(base.spec)
    job = base.config("terasort-1tb")
    for k in list(job["grid"])[3:]:
        job["grid"][k] = job["grid"][k][:1]
    fleet = base.config("mr-cluster-fb2009")
    fleet["jobs_per_trace"] = 24
    agg = fleet["job_types"]["aggregate_fast"]      # one 30-map job with a shuffle
    agg.update(jobs=80000, input_bytes=2e9, shuffle_bytes=8e7, output_bytes=5e6)
    fleet["grid"].update(pNumNodes=[2.0, 8.0], pMaxMapsPerNode=[2.0, 8.0],
                         pMaxRedPerNode=[1.0, 2.0], arrivalRate=[0.05, 0.2])
    for c, cfg in zip(spec["configs"], (job, fleet)):
        (d / f"{c['name']}.json").write_text(json.dumps(cfg))
        c["file"] = str(d / f"{c['name']}.json")
    svc = base.traffic("service-open")
    svc["rate_qps"] = 30
    svc["kinds"][-1]["rows"] = [8, 16]
    svc["check_queries"] = 8
    (d / "service-open.json").write_text(json.dumps(svc))
    for name in ("grid-topk", "planner-grid"):
        (d / f"{name}.json").write_text(json.dumps(base.traffic(name)))
    return Bench(ROOT, spec=spec, traffic_dir=d)


def _run(bench, cell, seed=2**31 + 11, seconds=0.6, trace=False, **kw):
    with jax.enable_x64(False):
        return run_cell(bench, cell, seed=seed, seconds=seconds, trace=trace,
                        devs=jax.devices(), t_start=time.perf_counter(), **kw)


def _schema(out, bench, cell, kind):
    assert list(out)[:3] == ["correct", "attempted", "failed"]
    assert list(out)[-1] == "checks"
    names = {m["name"] for m in bench.metrics(cell, kind)}
    assert set(out["metrics"]) <= names
    for m in out["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] == m["value"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(out["device"])
    assert all(set(c) == {"value", "limit"} for c in out["checks"].values())


@pytest.mark.parametrize("cell", [GRID, SERVICE, PLANNER])
def test_cell_runs_correct_with_its_end_to_end_metrics(bench, cell):
    out = _run(bench, cell)
    _schema(out, bench, cell, "end_to_end")
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {m["name"] for m in bench.metrics(cell, "end_to_end")}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert out["attempted"] > 0 and out["failed"] == 0


@pytest.mark.parametrize("cell", [GRID, SERVICE])
def test_traced_run_reads_its_span_metrics(bench, cell, tmp_path, monkeypatch):
    import perfbench.harness.runner as runner

    monkeypatch.setattr(runner, "OUT_DIR", tmp_path)
    out = _run(bench, cell, trace=True)
    _schema(out, bench, cell, "per_layer")
    assert out["correct"]
    want = {GRID: {"topk.chunk_ms", "search.host_share"},
            SERVICE: {"service.rows_per_chunk", "evaluate.ms_per_chunk"}}[cell]
    assert want <= set(out["metrics"])


def test_a_new_config_file_and_entry_add_a_cell(bench, tmp_path):
    spec = copy.deepcopy(bench.spec)
    cfg = bench.config("terasort-1tb")
    cfg["name"] = "terasort-100gb"
    cfg["params"]["pNumMappers"] = 746.0
    (tmp_path / "cfg.json").write_text(json.dumps(cfg))
    spec["configs"].append({"name": "terasort-100gb", "source": "x", "file": str(tmp_path / "cfg.json"),
                            "reduced": [], "why": "x"})
    spec["workloads"].append({"name": "whatif-terasort100-grid", "config": "terasort-100gb",
                              "traffic": "grid-topk", "chips": 1, "why": "x"})
    for m in spec["end_to_end"]:
        if m["name"] == "configs_per_s":
            m["workloads"].append("whatif-terasort100-grid")
    new = Bench(ROOT, spec=spec, traffic_dir=bench.traffic_dir)
    out = _run(new, "whatif-terasort100-grid")
    assert out["correct"] and out["metrics"]["configs_per_s"]["value"] > 0


@pytest.mark.parametrize("cell", [GRID, SERVICE, PLANNER])
def test_control_in_the_next_precision_down_is_not_correct(bench, cell):
    out = _run(bench, cell, **control_kw(LOAD[cell]))
    assert not out["correct"]
    gap = out["checks"]["p95_gap_ulp" if cell == PLANNER else "rel_err"]
    assert gap["value"] > gap["limit"] \
        or out["checks"].get("valid_mismatch", {"value": 0})["value"] > 0


class _Fault:
    """Delegates to the program's evaluator with one fault planted."""

    def __init__(self, inner, kind):
        self._inner, self._kind = inner, kind

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def chunk_topk(self, overrides, k):
        if self._kind == "half_batch":
            n = len(next(iter(overrides.values())))
            return self._inner.chunk_topk({c: v[: n // 2] for c, v in overrides.items()}, k)
        b = self._inner.chunk_topk(overrides, k)
        b.costs = np.asarray(b.costs) * np.float32(1.01)
        return b

    def evaluate(self, overrides):
        n = len(np.atleast_1d(next(iter(overrides.values()))))
        if self._kind == "half_batch":
            keep = max(1, n // 2)
            res = self._inner.evaluate({c: np.atleast_1d(v)[:keep] for c, v in overrides.items()})
            res.outputs = {c: np.resize(v, n) for c, v in res.outputs.items()}
            return res
        res = self._inner.evaluate(overrides)
        cost = res.outputs["j_totalCost"].copy()
        cost[::2] *= np.float32(1.001)
        res.outputs["j_totalCost"] = cost
        return res


@pytest.mark.parametrize("fault", ["altered_answer", "half_batch"])
@pytest.mark.parametrize("cell", [GRID, SERVICE, PLANNER])
def test_a_planted_fault_is_not_correct(bench, cell, fault):
    out = _run(bench, cell, plant=lambda ev: _Fault(ev, fault))
    assert not out["correct"], out["checks"]
