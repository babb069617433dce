"""The benchmark harness without a chip: discovery by name, the device gate,
the frozen work counts, the percentile arithmetic and the trace reduction."""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import subprocess  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

import pytest  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from perfbench.harness.bench import Bench  # noqa: E402
from perfbench.harness.device import PEAKS, DeviceGateError, gate  # noqa: E402
from perfbench.harness.stats import percentile  # noqa: E402
from perfbench.harness.trace import ProgramNotFound, module_base, reduce_events  # noqa: E402
from perfbench.harness.work import JOB_MODEL_FLOPS_PER_ROW, topk_body_work  # noqa: E402

BENCH = Bench(ROOT)
SPEC = BENCH.spec
CELLS = [w["name"] for w in SPEC["workloads"]]


@pytest.mark.parametrize("name", [c["name"] for c in SPEC["configs"]])
def test_every_config_is_found_by_name(name):
    cfg = BENCH.config(name)
    assert cfg["name"] == name and cfg["grid"]
    entry = next(c for c in SPEC["configs"] if c["name"] == name)
    assert entry["file"].startswith("perfbench/configs/")


@pytest.mark.parametrize("name", sorted({w["traffic"] for w in SPEC["workloads"]}))
def test_every_traffic_mix_is_found_by_name_with_its_load(name):
    import importlib

    mix = BENCH.traffic(name)
    drv = importlib.import_module(f"perfbench.loads.{mix['load']}").Load
    assert callable(drv) and mix["limits"]


@pytest.mark.parametrize("name", [m["name"] for m in SPEC["per_layer"]])
def test_every_per_layer_metric_has_a_reader_that_reads_nothing_from_nothing(name):
    read = BENCH.reader(name)
    empty = {"cell": "x", "window_s": 1.0, "trace": None, "spans": [], "evaluate_s": None,
             "peaks": PEAKS["TPU v5 lite"], "service": None, "rows_per_chunk": 8192,
             "swept_keys": 11, "num_devices": 1}
    assert read(empty) is None


def test_benchmark_json_names_are_well_formed():
    import re

    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
    names = ([c["name"] for c in SPEC["configs"]] + CELLS
             + [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]])
    assert all(name.match(n) for n in names) and len(set(names)) == len(names)
    for m in SPEC["per_layer"]:
        e2e = next(e for e in SPEC["end_to_end"] if e["name"] == m["moves"])
        for w in m.get("workloads", CELLS):
            assert "workloads" not in e2e or w in e2e["workloads"]
    for w in SPEC["workloads"]:
        assert any("workloads" not in e or w["name"] in e["workloads"]
                   for e in SPEC["end_to_end"] if e["name"] != "setup_s")


def test_device_gate_refuses_the_cpu():
    with pytest.raises(DeviceGateError):
        gate(1)


def test_entry_point_exits_nonzero_without_a_chip_and_prints_no_result():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run([sys.executable, "perfbench/run.py", "--workload", CELLS[0],
                        "--seed", str(2**31 + 5), "--seconds", "1", "--trace", "0"],
                       cwd=ROOT, capture_output=True, text=True, env=env, timeout=120)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_frozen_work_depends_on_shapes_only():
    f1, b1 = topk_body_work(8192, 11)
    assert (f1, b1) == (8192 * JOB_MODEL_FLOPS_PER_ROW, 8192 * 45)
    assert topk_body_work(16384, 11) == (2 * f1, 2 * b1)


def test_percentile_interpolates_between_order_statistics():
    import numpy as np

    v = sorted(np.random.default_rng(0).exponential(size=101).tolist())
    for p in (50, 95, 99):
        assert percentile(v, p) == pytest.approx(float(np.percentile(v, p)), rel=1e-12)
    assert percentile([], 95) == 0.0 and percentile([3.0], 95) == 3.0
    assert percentile([1.0, float("inf")], 95) == float("inf")


def test_trace_reduction_on_a_synthetic_trace():
    ms = 1_000_000
    dev, ops, mods = "/device:TPU:0", "XLA Ops", "XLA Modules"
    events = [
        ("/host:CPU", "python", "perfbench:window", 0, 100 * ms),
        ("/host:CPU", "python", "perfbench:search", 0, 100 * ms),
        ("/host:CPU", "python", "perfbench:chunk_topk", 10 * ms, 30 * ms),
        (dev, mods, "jit__unknown(3)", 20 * ms, 10 * ms),
        (dev, ops, "fusion.1", 20 * ms, 6 * ms),
        (dev, ops, "fusion.2", 24 * ms, 6 * ms),          # overlaps fusion.1
        (dev, ops, "copy", 90 * ms, 20 * ms),             # runs past the window
    ]
    tr = reduce_events(events)
    assert tr["window_s"] == pytest.approx(0.1)
    assert tr["busy_s"] == pytest.approx(0.010 + 0.010)
    assert tr["modules"] == {"jit__unknown": (1, pytest.approx(0.010))}
    idle = dict(tr["idle_gaps"])
    assert idle["host in chunk_topk"] == pytest.approx(0.030 - 0.010)
    assert idle["host in search"] == pytest.approx(0.1 - 0.02 - 0.02)
    assert sum(idle.values()) == pytest.approx(0.1 - tr["busy_s"])
    assert module_base("jit_per_device(12)") == "jit_per_device"


@pytest.mark.parametrize("cell, program", [("whatif-terasort-grid", "jit__unknown"),
                                           ("whatif-terasort-service", "jit_per_device"),
                                           ("planner-fb2009-hour", "jit_per_device")])
def test_trace_reduction_on_a_recorded_chip_trace(cell, program):
    """The first few hundred device events of a traced run on a TPU v5e, with
    the harness's host spans over them: the reduction finds the cell's
    program by its module name as the chip reports it (a hash in brackets
    after it), and busy and idle time add up to the window."""
    import json

    rec = json.loads((ROOT / "perfbench/tests/data" / f"chip-trace-{cell}.json").read_text())
    lo, width = rec["window"]
    events = [tuple(e) for e in rec["events"]]
    events.append(("/host:CPU", "python3", "perfbench:window", lo, width))
    tr = reduce_events(events)
    assert tr["devices"] == 1 and tr["window_s"] == pytest.approx(width * 1e-9)
    assert program in tr["modules"] and tr["modules"][program][0] > 0
    assert 0 < tr["busy_s"] < tr["window_s"]
    assert sum(s for _, s in tr["idle_gaps"]) == pytest.approx(tr["window_s"] - tr["busy_s"])
    assert tr["device_ops"] and all(s > 0 for _, s in tr["device_ops"])


def test_device_trace_readers_on_a_synthetic_trace():
    """The readers of the device trace: idle share, the rollout's device time
    per batch and the top-k program's roofline share, by hand."""
    tr = {"window_s": 1.0, "busy_s": 0.25, "devices": 1,
          "modules": {"jit__unknown": (4, 0.004), "jit_per_device": (2, 0.2)}}
    run = {"trace": tr, "spans": [("chunk_topk", 0.0, 0.1)] * 4,
           "peaks": PEAKS["TPU v5 lite"], "rows_per_chunk": 8192, "swept_keys": 11,
           "num_devices": 1}
    assert BENCH.reader("device_idle.configs")(run) == pytest.approx(75.0)
    rollout = {**run, "spans": run["spans"][:2]}
    assert BENCH.reader("rollout.device_ms_per_batch")(rollout) == pytest.approx(100.0)
    flops, nbytes = topk_body_work(8192, 11)
    least = max(flops / 197e12, nbytes / 819e9)         # the bytes bound binds
    assert least == nbytes / 819e9
    assert BENCH.reader("topk_body_roofline")(run) == pytest.approx(100 * least / 0.001)


@pytest.mark.parametrize("modules", [{}, {"jit__unknown": (5, 0.005), "jit_per_device": (3, 0.3)}],
                         ids=["missing", "shared"])
@pytest.mark.parametrize("metric", ["topk_body_roofline", "rollout.device_ms_per_batch"])
def test_a_device_trace_reader_fails_where_its_program_is_missing_or_shared(metric, modules):
    """A program renamed, or run by other calls too, fails the run; its
    metric is neither dropped nor fed other programs' time."""
    tr = {"window_s": 1.0, "busy_s": 0.25, "devices": 1, "modules": modules}
    run = {"trace": tr, "spans": [("chunk_topk", 0.0, 0.1)] * 4, "peaks": PEAKS["TPU v5 lite"],
           "rows_per_chunk": 8192, "swept_keys": 11, "num_devices": 1}
    with pytest.raises(ProgramNotFound):
        BENCH.reader(metric)(run)


@pytest.mark.parametrize("fair, want", [(0, [20.0, 24.0]), (1, [25.0, 29.0])], ids=["fifo", "fair"])
def test_wave_reference_shares_slots_by_hand(fair, want):
    """Two map-only jobs on two slots: job 0 (4 maps of 10 s) at t=0, job 1
    (2 maps of 5 s) at t=1.  FIFO serves job 0 first; fair splits the slots
    at t=10, hands the slot freed at t=15 to job 0 (share 0, spilled in
    arrival order), whose bucket then ends at 25."""
    from perfbench.reference import wave_ref

    lat, conv, waited = wave_ref.simulate(
        arrival=[[0.0, 1.0]], n_maps=[[4.0, 2.0]], n_reds=[[0.0, 0.0]], map_dur=[[10.0, 5.0]],
        shuffle=[[0.0, 0.0]], red_work=[[0.0, 0.0]], map_slots=[2.0], red_slots=[1.0],
        fair=[fair], slowstart=[0.05], n_steps=64)
    assert lat.tolist() == [want] and conv.tolist() == [True] and waited.tolist() == [True]


def test_wave_reference_stalls_an_early_reduce_and_caps_its_events():
    """4 maps of 10 s on 2 slots, one reducer: slowstart lets it launch at
    t=10 while 2 maps still run, so it stalls and ends at max(20, 10 + 4) + 2;
    with a cap of 2 events it has not converged."""
    from perfbench.reference import wave_ref

    cols = dict(arrival=[[0.0]], n_maps=[[4.0]], n_reds=[[1.0]], map_dur=[[10.0]],
                shuffle=[[4.0]], red_work=[[2.0]], map_slots=[2.0], red_slots=[1.0],
                fair=[0], slowstart=[0.05])
    lat, conv, _ = wave_ref.simulate(**cols, n_steps=64)
    assert lat.tolist() == [[22.0]] and conv.tolist() == [True]
    lat, conv, _ = wave_ref.simulate(**cols, n_steps=2)
    assert conv.tolist() == [False] and lat.tolist() == [[float("inf")]]
