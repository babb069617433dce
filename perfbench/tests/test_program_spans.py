"""The program's own spans and counters in a traced run, without a chip: the
readers of ``run["program"]``, the idle attribution with ``repro:`` spans,
and ``tools/program_spans.py`` end to end on the CPU at a tiny size."""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

import pytest  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402

from perfbench.harness import program_trace, trace  # noqa: E402
from perfbench.harness.bench import Bench  # noqa: E402
from perfbench.tools.program_spans import PROGRAM_METRICS, traced_run  # noqa: E402
from test_perfbench_cells import GRID, PLANNER, SERVICE, bench  # noqa: E402, F401 (fixture)

BENCH = Bench(ROOT)
READERS = sorted(n for names in PROGRAM_METRICS.values() for n in names)
MS = 1_000_000


@pytest.mark.parametrize("name", READERS)
def test_a_program_reader_reads_nothing_from_nothing(name):
    read = BENCH.reader(name)
    assert read({"cell": "x", "window_s": 1.0, "trace": None, "spans": []}) is None
    assert read({"program": {"histograms": {}, "counters": {}}}) is None


def test_program_readers_by_hand():
    run = {"program": {
        "histograms": {"evaluator.prepare_s": [0.010, 0.014],
                       "evaluator.dispatch_s": [0.001, 0.003],
                       "evaluator.fetch_s": [0.004, 0.008],
                       "service.queue_wait_s": [0.5, 0.01, 0.02, 0.03],
                       "cluster.build_scenarios_s": [0.015, 0.017, 0.019]},
        "counters": {"evaluator.d2h_bytes": 3 * 3 * 1024 * 1024, "service.queries": 3,
                     "vector_sim.lane_steps": 300, "vector_sim.loop_steps": 400}}}
    want = {"topk.prepare_ms": 12.0, "topk.dispatch_ms": 2.0, "topk.fetch_ms": 6.0,
            "evaluate.fetch_ms": 6.0, "service.queue_wait_ms": 25.0,
            "service.d2h_kib_per_query": 3 * 1024.0, "planner.build_ms": 17.0,
            "rollout.lane_use": 75.0}
    assert {n: BENCH.reader(n)(run) for n in READERS} == pytest.approx(want)


def _chunk_trace(with_program: bool) -> list[tuple]:
    dev, host = "/device:TPU:0", "/host:CPU"
    events = [
        (host, "python", "perfbench:window", 0, 100 * MS),
        (host, "python", "perfbench:search", 0, 100 * MS),
        (host, "python", "perfbench:chunk_topk", 10 * MS, 30 * MS),
        (dev, "XLA Modules", "jit__unknown(3)", 25 * MS, 5 * MS),
        (dev, "XLA Ops", "fusion.1", 25 * MS, 5 * MS),
    ]
    if with_program:
        events += [
            (host, "python", "repro:evaluator.chunk_topk", 11 * MS, 28 * MS),
            (host, "python", "repro:evaluator.prepare", 11 * MS, 9 * MS),
            (host, "python", "repro:evaluator.dispatch", 20 * MS, 1 * MS),
            (host, "python", "repro:evaluator.fetch", 21 * MS, 18 * MS),
        ]
    return events


def test_program_spans_take_their_share_of_the_idle_time():
    """A ``repro:`` span inside the harness's ``chunk_topk`` takes the idle
    time under it, innermost first; the harness span keeps what is left."""
    tr = program_trace.reduce_events(_chunk_trace(with_program=True))
    idle = dict(tr["idle_gaps"])
    assert idle == pytest.approx({
        "host in evaluator.prepare": 0.009, "host in evaluator.dispatch": 0.001,
        "host in evaluator.fetch": 0.018 - 0.005, "host in chunk_topk": 0.002,
        "host in search": 0.070})
    assert sum(idle.values()) == pytest.approx(tr["window_s"] - tr["busy_s"])


@pytest.mark.parametrize("cell", ["synthetic", GRID, SERVICE, PLANNER])
def test_without_program_spans_the_reduction_is_the_harness_one(cell):
    if cell == "synthetic":
        events = _chunk_trace(with_program=False)
    else:
        rec = json.loads((ROOT / "perfbench/tests/data" / f"chip-trace-{cell}.json").read_text())
        events = [tuple(e) for e in rec["events"]]
        events.append(("/host:CPU", "python3", "perfbench:window", *rec["window"]))
    assert program_trace.reduce_events(events) == trace.reduce_events(events)


def test_program_spans_split_the_idle_time_of_a_recorded_chip_trace():
    """A few hundred device events of a traced grid run on a TPU v5e with the
    program's spans live (``tools/program_spans.py --sample``): the idle time
    the harness alone puts under ``chunk_topk`` goes to the program's
    ``evaluator.*`` spans, the casts of ``prepare`` first."""
    rec = json.loads((ROOT / "perfbench/tests/data"
                      / "chip-trace-spans-whatif-terasort-grid.json").read_text())
    events = [tuple(e) for e in rec["events"]]
    events.append(("/host:CPU", "python3", "perfbench:window", *rec["window"]))
    chunk = dict(trace.reduce_events(events)["idle_gaps"])["host in chunk_topk"]
    tr = program_trace.reduce_events(events)
    idle = dict(tr["idle_gaps"])
    program = {k: v for k, v in idle.items() if k.startswith("host in evaluator.")}
    assert set(program) >= {"host in evaluator.prepare", "host in evaluator.dispatch",
                            "host in evaluator.fetch"}
    assert max(program, key=program.get) == "host in evaluator.prepare"
    assert sum(program.values()) >= 0.9 * chunk
    assert idle["host in chunk_topk"] < 0.1 * chunk
    assert sum(idle.values()) == pytest.approx(tr["window_s"] - tr["busy_s"])
    calls = tr["modules"]["jit__unknown"][0]
    assert tr["modules"]["jit_convert_element_type"][0] == 11 * calls


@pytest.mark.parametrize("cell", [GRID, SERVICE, PLANNER])
def test_program_spans_tool_on_the_cpu(bench, cell, tmp_path, monkeypatch):
    """The tool's traced run at a tiny size: every program-span metric of the
    cell is read, and each lies where the program's structure puts it."""
    import perfbench.harness.runner as runner

    monkeypatch.setattr(runner, "OUT_DIR", tmp_path)
    with jax.enable_x64(False):
        out = traced_run(bench, cell, seed=2**31 + 17, seconds=0.6, devs=jax.devices())
    m = out["metrics"]
    assert set(PROGRAM_METRICS[cell]) <= set(m) and out["program_events"] > 0
    if cell == GRID:
        parts = m["topk.prepare_ms"] + m["topk.dispatch_ms"] + m["topk.fetch_ms"]
        assert 0 < parts <= m["topk.chunk_ms"]
    elif cell == SERVICE:
        assert 0 < m["evaluate.fetch_ms"] <= m["evaluate.ms_per_chunk"]
        assert m["service.d2h_kib_per_query"] > 0
    else:
        assert m["planner.build_ms"] > 0 and 0 < m["rollout.lane_use"] <= 100
