"""repro.obs — metrics registry, Chrome-trace tracer, and the contract
the whole stack's instrumentation hangs off.

Three layers of coverage:

* the primitives: counter/gauge/histogram semantics, snapshot/merge,
  exact percentile interpolation (against numpy's linear method), the
  null singletons' zero-surface;
* the trace format: every emitted event is schema-valid Chrome trace
  JSON (required keys per phase, balanced B/E per track, monotonic
  timestamps), and off-by-default means *zero* events recorded;
* the integrations: DES virtual-time swimlanes (golden: deterministic,
  phase-carved, shuffle_end invariant), the evaluator under
  ``api.observe`` (same numbers, live counters), the spans that split a
  chunk, a rollout and a query's wait (in ``jax.profiler``'s own trace
  too) and their counters, the serve-loop's read-only stats view, and
  calibration's grad-norm series.
"""

import json

import numpy as np
import pytest

from repro.obs import (
    NULL_OBS,
    NULL_REGISTRY,
    NULL_TRACER,
    MetricsRegistry,
    Tracer,
    current,
    observe,
    percentile_interp,
)

# ------------------------------------------------------------------
# metrics primitives
# ------------------------------------------------------------------


def test_percentile_interp_matches_numpy_linear():
    rng = np.random.default_rng(0)
    xs = sorted(rng.normal(size=37).tolist())
    for p in (0.0, 1.0, 25.0, 50.0, 75.0, 90.0, 99.0, 100.0):
        assert percentile_interp(xs, p) == pytest.approx(
            float(np.percentile(xs, p)), rel=1e-12, abs=1e-12), p


def test_percentile_interp_edges():
    assert percentile_interp([], 50.0) == 0.0
    assert percentile_interp([7.0], 99.0) == 7.0
    assert percentile_interp([1.0, 2.0], -5.0) == 1.0
    assert percentile_interp([1.0, 2.0], 200.0) == 2.0


def test_counter_gauge_histogram_and_snapshot():
    reg = MetricsRegistry()
    reg.counter("c").inc()
    reg.counter("c").inc(4)
    reg.gauge("g").set(2.5)
    reg.gauge("g").add(0.5)
    for v in (1.0, 2.0, 3.0, 4.0):
        reg.histogram("h").record(v)
    snap = reg.snapshot()
    assert snap["c"] == 5 and isinstance(snap["c"], int)
    assert snap["g"] == 3.0
    h = snap["h"]
    assert h["count"] == 4 and h["sum"] == 10.0 and h["mean"] == 2.5
    assert h["min"] == 1.0 and h["max"] == 4.0
    assert h["p50"] == pytest.approx(2.5)
    # JSON export round-trips
    assert json.loads(reg.to_json())["c"] == 5
    with pytest.raises(ValueError):
        reg.counter("c").inc(-1)


def test_registry_kind_mismatch_raises():
    reg = MetricsRegistry()
    reg.counter("x")
    with pytest.raises(TypeError, match="x"):
        reg.gauge("x")


def test_registry_merge():
    a, b = MetricsRegistry(), MetricsRegistry()
    a.counter("n").inc(2)
    b.counter("n").inc(3)
    a.gauge("g").set(1.0)
    b.gauge("g").set(9.0)
    a.histogram("h").record(1.0)
    b.histogram("h").record(3.0)
    a.merge(b)
    snap = a.snapshot()
    assert snap["n"] == 5
    assert snap["g"] == 9.0            # gauges: last write wins
    assert snap["h"]["count"] == 2 and snap["h"]["sum"] == 4.0


def test_null_registry_is_inert():
    assert not NULL_REGISTRY.enabled
    NULL_REGISTRY.counter("x").inc(10)
    NULL_REGISTRY.gauge("y").set(1.0)
    NULL_REGISTRY.histogram("z").record(2.0)
    assert NULL_REGISTRY.snapshot() == {}
    live = MetricsRegistry()
    live.counter("k").inc()
    NULL_REGISTRY.merge(live)
    assert NULL_REGISTRY.snapshot() == {}


# ------------------------------------------------------------------
# LatencyStats (runtime.batching) — built on percentile_interp
# ------------------------------------------------------------------


def test_latency_stats_percentiles_and_small_samples():
    from repro.runtime.batching import LatencyStats

    empty = LatencyStats()
    assert empty.count == 0 and empty.p50 == 0.0 and empty.p99 == 0.0

    one = LatencyStats()
    one.record(0.25)
    assert one.p50 == 0.25 and one.p99 == 0.25 and one.mean() == 0.25

    many = LatencyStats()
    rng = np.random.default_rng(1)
    xs = rng.exponential(size=101).tolist()
    for x in xs:
        many.record(x)
    for p in (50.0, 90.0, 99.0):
        assert many.percentile(p) == pytest.approx(
            float(np.percentile(xs, p)), rel=1e-12)


def test_latency_stats_merge_pools_samples():
    from repro.runtime.batching import LatencyStats

    a, b = LatencyStats(), LatencyStats()
    for x in (1.0, 2.0):
        a.record(x)
    for x in (3.0, 4.0):
        b.record(x)
    assert a.merge(b) is a
    assert a.count == 4
    assert a.mean() == pytest.approx(2.5)
    assert b.count == 2                # source unchanged


# ------------------------------------------------------------------
# trace format
# ------------------------------------------------------------------


def _assert_valid_chrome_trace(events):
    """Schema validity + balanced/monotonic B/E per (pid, tid) track."""
    open_spans: dict[tuple, list] = {}
    last_ts: dict[tuple, float] = {}
    for e in events:
        assert isinstance(e.get("name"), str) and e["name"], e
        assert "ph" in e and "pid" in e and "tid" in e, e
        ph = e["ph"]
        if ph == "M":
            continue
        ts = e["ts"]
        assert isinstance(ts, (int, float)) and ts >= 0.0, e
        key = (e["pid"], e["tid"])
        if ph in ("B", "E"):
            assert ts >= last_ts.get(key, 0.0), f"ts went backwards: {e}"
            last_ts[key] = ts
            stack = open_spans.setdefault(key, [])
            if ph == "B":
                stack.append(e["name"])
            else:
                assert stack and stack[-1] == e["name"], (
                    f"unbalanced E {e['name']!r}; open: {stack}")
                stack.pop()
        elif ph == "X":
            assert e.get("dur", -1.0) >= 0.0, e
        elif ph == "i":
            assert e.get("s") in ("t", "p", "g"), e
        elif ph == "C":
            assert isinstance(e.get("args"), dict) and e["args"], e
        elif ph in ("b", "e", "n"):
            assert "id" in e and "cat" in e, e
        else:
            pytest.fail(f"unknown phase {ph!r}: {e}")
    for key, stack in open_spans.items():
        assert not stack, f"unclosed spans on {key}: {stack}"


def test_tracer_emits_schema_valid_events():
    tr = Tracer()
    tr.process_name(1, "test")
    tr.thread_name(1, 7, "lane", sort_index=7)
    with tr.span("outer", depth=0):
        with tr.span("inner"):
            tr.instant("tick", scope="p")
        tr.counter("load", depth=1.5)
    tr.complete("done", tr.now_us(), 10.0, pid=3, tid=4)
    tr.async_begin("q", 42)
    tr.async_instant("q-progress", 42)
    tr.async_end("q", 42)
    events = tr.events()
    assert len(events) >= 10
    _assert_valid_chrome_trace(events)
    doc = json.loads(tr.to_json())
    assert list(doc) == ["traceEvents"]
    assert len(doc["traceEvents"]) == len(events)


def test_tracer_write(tmp_path):
    tr = Tracer()
    with tr.span("s"):
        pass
    out = tmp_path / "t.json"
    tr.write(str(out))
    assert json.loads(out.read_text())["traceEvents"]


def test_span_unwinds_on_exception():
    tr = Tracer()
    with pytest.raises(RuntimeError):
        with tr.span("bad"):
            raise RuntimeError("boom")
    _assert_valid_chrome_trace(tr.events())   # E still emitted


# ------------------------------------------------------------------
# off-by-default: the null path records nothing
# ------------------------------------------------------------------


def test_ambient_defaults_to_null_and_observe_restores():
    assert current() is NULL_OBS
    assert not current().enabled
    with observe() as ob:
        assert current() is ob and ob.enabled
        with observe() as inner:                  # contexts nest
            assert current() is inner
        assert current() is ob
    assert current() is NULL_OBS


def test_null_tracer_records_nothing():
    with NULL_TRACER.span("x", a=1):
        NULL_TRACER.instant("i")
        NULL_TRACER.counter("c", v=1)
    NULL_TRACER.complete("x", 0.0, 1.0)
    assert NULL_TRACER.events() == []
    assert not NULL_TRACER.enabled


def test_uninstrumented_run_touches_no_ambient_state():
    """A DES run with observability off must leave the null singletons
    empty — the guard is `ob.enabled`, checked before any recording."""
    from repro.cluster import (
        ClusterConfig,
        JobArrival,
        JobClass,
        WorkloadTrace,
        simulate_workload,
    )
    from repro.core.hadoop.params import CostFactors, HadoopParams, MiB, ProfileStats
    from repro.core.hadoop.simulator import SimConfig

    p = HadoopParams(pNumNodes=2, pNumMappers=8, pNumReducers=2,
                     pSplitSize=64 * MiB)
    jc = JobClass("one", p, ProfileStats(), CostFactors())
    tr = WorkloadTrace((JobArrival(0, jc, 0.0),))
    assert current() is NULL_OBS
    simulate_workload(tr, ClusterConfig(num_nodes=2),
                      SimConfig(speculative_execution=False))
    assert NULL_TRACER.events() == []
    assert NULL_REGISTRY.snapshot() == {}


def test_observe_writes_trace_file(tmp_path):
    out = tmp_path / "obs.json"
    with observe(str(out)) as ob:
        with ob.tracer.span("work"):
            ob.registry.counter("n").inc()
    doc = json.loads(out.read_text())
    assert [e["name"] for e in doc["traceEvents"] if e["ph"] == "B"] == ["work"]


# ------------------------------------------------------------------
# DES virtual-time swimlanes (golden on the canonical one-job workload)
# ------------------------------------------------------------------


def _one_job_des():
    from repro.cluster import (
        ClusterConfig,
        JobArrival,
        JobClass,
        WorkloadTrace,
        simulate_workload,
    )
    from repro.core.hadoop.params import CostFactors, HadoopParams, MiB, ProfileStats
    from repro.core.hadoop.simulator import SimConfig

    p = HadoopParams(pNumNodes=4, pNumMappers=32, pNumReducers=8,
                     pSplitSize=64 * MiB)
    jc = JobClass("one", p, ProfileStats(), CostFactors())
    tr = WorkloadTrace((JobArrival(0, jc, 0.0),))
    cc = ClusterConfig.from_params(p)
    res = simulate_workload(tr, cc, SimConfig(speculative_execution=False))
    return tr, res, cc


MAP_PHASES = {"map_read", "map_spill", "map_merge", "map_write"}
REDUCE_PHASES = {"network", "shuffle", "reduce_merge", "reduce_write"}


def test_workload_trace_golden_one_job():
    from repro.obs import workload_trace
    from repro.obs.destrace import SIM_SECOND_US

    tr, res, cc = _one_job_des()
    events = workload_trace(tr, res, cc).events()
    _assert_valid_chrome_trace(events)

    # deterministic: same simulation -> identical event list (virtual time)
    again = workload_trace(tr, res, cc).events()
    assert events == again

    xs = [e for e in events if e["ph"] == "X"]
    task_spans = [e for e in xs if "[" in e["name"]]
    phase_spans = [e for e in xs if e["name"] in MAP_PHASES | REDUCE_PHASES]
    assert len(task_spans) == 32 + 8          # every map + reduce rendered
    assert {e["name"] for e in phase_spans} >= {
        "map_read", "map_spill", "network", "reduce_write"}

    # virtual-time axis: the last span ends at the simulated makespan
    end_us = max(e["ts"] + e["dur"] for e in xs)
    assert end_us == pytest.approx(res.makespan * SIM_SECOND_US, rel=1e-9)

    # per-job lane: queued + running spans, running ends at job finish
    job = res.jobs[0]
    running = [e for e in xs if e["name"] == "running"]
    assert len(running) == 1
    assert running[0]["ts"] + running[0]["dur"] == pytest.approx(
        job.finish * SIM_SECOND_US)

    # counter sweep present, on tid 0
    counters = [e for e in events if e["ph"] == "C"]
    assert counters and all(e["tid"] == 0 for e in counters)
    assert {"maps", "reduces"} <= set(counters[0]["args"])


def test_des_records_shuffle_end_invariant():
    _, res, _ = _one_job_des()
    reduces = [r for r in res.records if r.kind == "reduce" and not r.killed]
    assert reduces
    for r in reduces:
        assert r.start <= r.shuffle_end <= r.end
    for r in res.records:
        assert (r.kill_reason != "") == r.killed


def test_des_simulate_records_metrics_under_observe():
    from repro.cluster import (
        ClusterConfig,
        JobArrival,
        JobClass,
        WorkloadTrace,
        simulate_workload,
    )
    from repro.core.hadoop.params import CostFactors, HadoopParams, MiB, ProfileStats
    from repro.core.hadoop.simulator import SimConfig

    p = HadoopParams(pNumNodes=2, pNumMappers=8, pNumReducers=2,
                     pSplitSize=64 * MiB)
    jc = JobClass("one", p, ProfileStats(), CostFactors())
    tr = WorkloadTrace((JobArrival(0, jc, 0.0),))
    with observe() as ob:
        res = simulate_workload(tr, ClusterConfig(num_nodes=2),
                                SimConfig(speculative_execution=False))
    snap = ob.registry.snapshot()
    assert snap["des.runs"] == 1 and snap["des.jobs"] == 1
    assert snap["des.tasks"] == len(res.records)
    assert [e["name"] for e in ob.tracer.events()
            if e["ph"] == "X"] == ["des.simulate"]


# ------------------------------------------------------------------
# evaluator + api.observe: live counters, unchanged numbers
# ------------------------------------------------------------------


def test_api_observe_evaluator_counters_and_equivalence(tmp_path):
    import repro.api as api
    from repro.core.hadoop.params import CostFactors, HadoopParams, MiB, ProfileStats
    from repro.search import ChunkedEvaluator

    hp = HadoopParams(pNumNodes=4, pNumMappers=32, pNumReducers=8,
                      pSplitSize=64 * MiB)
    ev = ChunkedEvaluator(hp, ProfileStats(), CostFactors(), chunk=64)
    rows = {"pSortMB": np.array([50.0, 100.0, 200.0])}
    plain = ev.evaluate(rows)
    out = tmp_path / "ev.json"
    with api.observe(str(out)) as ob:
        traced = ev.evaluate(rows)
    assert np.array_equal(plain.total_cost, traced.total_cost)
    snap = ob.registry.snapshot()
    assert snap["evaluator.rows"] == 3
    assert snap["evaluator.chunks"] >= 1
    assert snap["evaluator.evaluate_s"]["count"] == 1
    doc = json.loads(out.read_text())
    _assert_valid_chrome_trace(doc["traceEvents"])
    assert any(e["name"] == "evaluator.evaluate"
               for e in doc["traceEvents"])


def _small_evaluator(chunk=64):
    from repro.core.hadoop.params import CostFactors, HadoopParams, MiB, ProfileStats
    from repro.search import ChunkedEvaluator

    hp = HadoopParams(pNumNodes=4, pNumMappers=32, pNumReducers=8,
                      pSplitSize=64 * MiB)
    return ChunkedEvaluator(hp, ProfileStats(), CostFactors(), chunk=chunk)


def test_span_helper_times_into_a_histogram_and_is_free_when_off():
    from repro.obs import _TimedSpan
    from repro.obs.trace import _NULL_SPAN

    assert NULL_OBS.span("x", a=1) is _NULL_SPAN
    with observe() as ob:
        for _ in range(3):
            with ob.span("outer", n=1):
                with ob.span("inner"):
                    pass
        assert isinstance(ob.span("y"), _TimedSpan)
    inner = ob.registry.histogram("inner_s").samples()
    outer = ob.registry.histogram("outer_s").samples()
    assert len(inner) == len(outer) == 3
    assert all(0.0 <= i <= o for i, o in zip(inner, outer))
    _assert_valid_chrome_trace(ob.tracer.events())
    assert [e["name"] for e in ob.tracer.events() if e["ph"] == "B"] == \
        ["outer", "inner"] * 3


def test_live_spans_land_in_the_profiler_trace_nested(tmp_path):
    """A live tracer's spans reach jax.profiler's own trace as repro:<name>
    host events, on the profiler's clock: the fetch lies inside the chunk's
    span and starts after the dispatch ended."""
    import jax
    from jax.profiler import ProfileData

    ev = _small_evaluator()
    rows = {"pSortMB": np.array([50.0, 100.0, 200.0])}
    ev.chunk_topk(rows, 2)                        # compile outside the capture
    jax.profiler.start_trace(str(tmp_path))
    try:
        with observe():
            ev.chunk_topk(rows, 2)
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("**/*.xplane.pb")
    spans = {}
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("repro:"):
                    spans[e.name] = (e.start_ns, e.start_ns + e.duration_ns)
    assert set(spans) == {"repro:evaluator.chunk_topk", "repro:evaluator.prepare",
                          "repro:evaluator.dispatch", "repro:evaluator.fetch"}
    top = spans["repro:evaluator.chunk_topk"]
    fetch = spans["repro:evaluator.fetch"]
    assert top[0] <= fetch[0] and fetch[1] <= top[1]
    assert fetch[0] >= spans["repro:evaluator.dispatch"][1]


def test_evaluator_spans_split_each_chunk_and_count_fetched_bytes():
    ev = _small_evaluator(chunk=64)
    rows = {"pSortMB": np.linspace(16.0, 400.0, 150)}      # 3 chunks
    with observe() as ob:
        res = ev.evaluate(rows)
        copies = ob.registry.counter("evaluator.d2h_copies").value
        ev.chunk_topk({"pSortMB": rows["pSortMB"][:40]}, 5)
    reg = ob.registry
    # one packed copy per chunk (every output is float); the top-k path
    # counts none
    assert copies == 3
    assert reg.counter("evaluator.d2h_copies").value == 3
    count = {n: reg.histogram(f"evaluator.{n}_s").count
             for n in ("prepare", "evaluate", "dispatch", "fetch", "chunk_topk")}
    # evaluate: one split, 3 chunks; chunk_topk: one of each
    assert count == {"prepare": 2, "evaluate": 1, "dispatch": 4, "fetch": 4,
                     "chunk_topk": 1}
    import jax

    # every output column of each 64-row chunk, and everything the top-k
    # program returns for its block
    per_chunk = sum(64 * v.itemsize for v in res.outputs.values())
    batched, static, n = ev._split({"pSortMB": rows["pSortMB"][:40]})
    cols, mask = ev._pad(batched, 0, n)
    topk = sum(np.asarray(a).nbytes for a in jax.tree_util.tree_leaves(
        ev._topk_fn(cols, static, mask, k=5)))
    assert reg.counter("evaluator.d2h_bytes").value == 3 * per_chunk + topk
    assert reg.counter("evaluator.chunks").value == 3
    assert reg.counter("evaluator.rows").value == 190


def _wave_lane(n_maps, arrivals, slots=2.0, cost=10.0):
    j = len(n_maps)
    return {"arrival": np.asarray(arrivals, float),
            "n_maps": np.asarray(n_maps, float), "n_reds": np.zeros(j),
            "map_cost": np.full(j, cost), "red_work": np.zeros(j),
            "shuffle": np.zeros(j), "map_slots": np.asarray(slots),
            "red_slots": np.asarray(1.0), "slowstart": np.asarray(1.0)}


def _fifo_event_count(n_maps, arrivals, slots=2, cost=10.0):
    """Distinct event times of a map-only FIFO lane, by list scheduling:
    every arrival and every task end is one step of the rollout."""
    free = [0.0] * slots
    events = set(arrivals)
    for n, t in sorted(zip(n_maps, arrivals), key=lambda x: x[1]):
        for _ in range(n):
            i = int(np.argmin(free))
            free[i] = max(free[i], t) + cost
            events.add(free[i])
    return len(events)


def test_rollout_lane_counters_match_a_numpy_count_of_events():
    from repro.cluster.vector_sim import simulate_batch

    lanes = [([4, 1], [0.0, 0.0]), ([2, 1], [0.0, 35.0]),
             ([6, 3], [0.0, 3.0]), ([1, 1], [0.0, 0.0])]
    scen = {k: np.stack([_wave_lane(*ln)[k] for ln in lanes])
            for k in _wave_lane(*lanes[0])}
    steps = [_fifo_event_count(*ln) for ln in lanes]
    assert steps == [4, 4, 7, 2]
    with observe() as ob:
        simulate_batch(scen)
    reg = ob.registry
    assert reg.counter("vector_sim.lane_steps").value == sum(steps)
    assert reg.counter("vector_sim.loop_steps").value == len(lanes) * max(steps)
    for part in ("simulate_batch", "prepare", "dispatch", "fetch"):
        assert reg.histogram(f"vector_sim.{part}_s").count == 1


def test_tracing_leaves_outputs_bit_for_bit_and_keys_unchanged():
    from repro.cluster.vector_sim import simulate_batch

    ev = _small_evaluator()
    rows = {"pSortMB": np.linspace(16.0, 400.0, 50),
            "pNumReducers": np.arange(50, dtype=float)}
    scen = {k: np.stack([_wave_lane([3, 2], [0.0, 4.0])[k],
                         _wave_lane([5, 1], [0.0, 1.0])[k]])
            for k in _wave_lane([1], [0.0])}
    blk0, sim0 = ev.chunk_topk(rows, 4), simulate_batch(scen)
    with observe():
        blk, sim = ev.chunk_topk(rows, 4), simulate_batch(scen)
    for f in ("costs", "idx", "inv_costs", "inv_idx"):
        assert np.array_equal(getattr(blk0, f), getattr(blk, f)), f
    assert (blk0.n_valid, blk0.reason_counts) == (blk.n_valid, blk.reason_counts)
    assert sim.keys() == sim0.keys() and "steps" not in sim
    assert all(np.array_equal(sim0[k], sim[k]) for k in sim)


def test_queue_wait_is_one_sample_per_query_within_its_latency():
    from repro.search import WhatIfService

    ev = _small_evaluator(chunk=32)
    with observe() as ob:
        with WhatIfService(ev) as svc:
            futs = [svc.submit({"pSortMB": np.full(n, 50.0 + n)})
                    for n in (1, 40, 7, 70, 3)]
            lat = [f.result().stats.latency_s for f in futs]
    waits = ob.registry.histogram("service.queue_wait_s").samples()
    assert len(waits) == len(futs)
    assert all(w >= 0.0 for w in waits)
    # per query on its async track: submitted <= packed <= resolved
    marks = {}
    for e in ob.tracer.events():
        if e["ph"] in ("b", "n", "e"):
            marks.setdefault(e["id"], {})[e["ph"]] = e["ts"]
    assert len(marks) == len(futs)
    assert all(m["b"] <= m["n"] <= m["e"] for m in marks.values())
    # each wait is at most its own latency, so also order statistic-wise
    assert all(w <= x for w, x in zip(sorted(waits), sorted(lat)))


def test_observe_counts_compiles_through_the_jax_listener():
    import jax

    with observe() as ob:
        jax.jit(lambda x: x * 3.0 + 1.0)(np.arange(7.0))
    assert ob.registry.counter("jax.backend_compile_duration").value >= 1


# ------------------------------------------------------------------
# serve-loop stats view
# ------------------------------------------------------------------


def test_server_stats_view_reads_registry():
    from repro.runtime.serve_loop import _CounterView

    reg = MetricsRegistry()
    view = _CounterView(reg)
    assert set(view) == {"prefills", "decode_ticks", "tokens_out"}
    assert len(view) == 3
    assert view["prefills"] == 0
    reg.counter("server.prefills").inc(3)
    assert view["prefills"] == 3 and isinstance(view["prefills"], int)
    assert dict(view)["tokens_out"] == 0
    with pytest.raises(KeyError):
        view["no_such_counter"]


# ------------------------------------------------------------------
# calibration series
# ------------------------------------------------------------------


def test_calibrate_reports_grad_norm_series():
    from repro.calib import Observation, calibrate
    from repro.core.hadoop.model import job_model_jnp
    from repro.spec import JobSpec

    base = JobSpec()

    def total(s):
        return float(job_model_jnp(s.pack())["j_totalCost"])

    obs = [Observation(spec=s, cost=total(s))
           for s in (base.replace(pSortMB=mb) for mb in (64.0, 128.0))]
    with observe() as ob:
        rep = calibrate(obs, ["cMapCPUCost"], steps=20, history_every=5)
    assert len(rep.grad_norm_history) == len(rep.loss_history) - 1
    assert all(np.isfinite(g) for g in rep.grad_norm_history)
    assert rep.n_model_evals == 22
    snap = ob.registry.snapshot()
    assert snap["calib.runs"] == 1 and snap["calib.model_evals"] == 22
    assert any(e["name"] == "calibration" for e in ob.tracer.events()
               if e["ph"] == "C")
