"""One run of one cell, after the device gate: set-up, the measured window,
the per-layer readings of a traced run, and the comparisons that decide
``correct``.  Returns the result line as a dict."""

from __future__ import annotations

import contextlib
import importlib
import os
import shutil
import sys
import time
from pathlib import Path

from perfbench.harness.bench import ROOT, Bench
from perfbench.harness.device import PEAKS, describe, memory_peak_bytes
from perfbench.harness.window import CompileCounter, Spans

__all__ = ["use_compile_cache", "run_cell", "OUT_DIR"]

#: where traced runs write the profiler's files (listed in .gitignore)
OUT_DIR = ROOT / ".perfbench_out"


def use_compile_cache(root: Path = ROOT) -> str:
    """JAX's persistent compilation cache at ``$JAX_COMPILATION_CACHE_DIR``
    where set, else at the fixed path ``<checkout>/.jax_cache``; every
    program is cached, however short its compile."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(Path(root) / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def _say(msg: str) -> None:
    print(f"[perfbench] {msg}", file=sys.stderr, flush=True)


@contextlib.contextmanager
def _profiled(on: bool, logdir: Path):
    if not on:
        yield
        return
    import jax

    shutil.rmtree(logdir, ignore_errors=True)
    jax.profiler.start_trace(str(logdir))
    try:
        yield
    finally:
        jax.profiler.stop_trace()


@contextlib.contextmanager
def _registry(on: bool):
    """The program's metrics registry, live in traced runs only."""
    if not on:
        yield None
        return
    from repro.obs import NULL_TRACER, observe

    with observe(tracer=NULL_TRACER) as ob:
        yield ob.registry


def _keep_sample(events: list, path: Path, n: int = 400) -> None:
    """A few hundred events from the window's start, for checking the
    trace reduction without a chip."""
    import json

    win = [e for e in events if e[2] == "perfbench:window"]
    if not win:
        return
    lo = win[0][3]
    dev = sorted((e for e in events if e[0].startswith("/device:") and e[3] >= lo),
                 key=lambda e: e[3])[:n]
    hi = dev[-1][3] + dev[-1][4] if dev else lo
    host = [e for e in events if e[0].startswith("/host") and e[3] < hi
            and e[3] + e[4] > lo and e[2] != "perfbench:window"]
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps({"window": [lo, hi - lo], "events": dev + host}))


def run_cell(bench: Bench, workload: str, *, seed: int, seconds: float, trace: bool,
             devs, t_start: float, **load_kw) -> dict:
    cell = bench.cell(workload)
    cfg = bench.config(cell["config"])
    traffic = bench.traffic(cell["traffic"])
    load_cls = importlib.import_module(f"perfbench.loads.{traffic['load']}").Load
    spans = Spans(annotate=trace)
    load = load_cls(cfg, traffic, seed, spans, **load_kw)
    load.setup()
    setup_s = time.perf_counter() - t_start
    if trace:
        seconds = min(seconds, float(traffic.get("trace_seconds", seconds)))
    counter = CompileCounter()
    logdir = OUT_DIR / f"trace-{workload}"
    with _registry(trace) as registry:
        spans.records.clear()
        with _profiled(trace, logdir):
            t0 = time.perf_counter()
            with counter.armed(), spans.span("window"):
                load.run(seconds)
            t1 = time.perf_counter()
        if hasattr(load, "drain"):
            load.drain()
        hist = registry.histogram("evaluator.evaluate_s").samples() if registry else None
    device = describe(devs)
    device["memory_peak_bytes"] = memory_peak_bytes(devs)
    attempted, failed = load.counts()
    _say(f"window {t1 - t0!r} s; compile events inside it: {dict(counter.counts)} "
         f"({counter.compiles} backend compiles)")
    for line in getattr(load, "window_notes", []):
        _say(line)

    out = {"correct": False, "attempted": attempted, "failed": failed}
    if trace:
        from perfbench.harness.trace import read_events, reduce_events

        events = read_events(str(logdir))
        tr = reduce_events(events)
        _keep_sample(events, OUT_DIR / f"events-{workload}.json")
        if tr is not None:
            _say(f"programs in the trace (calls, device seconds): {tr['modules']}")
        run = {"cell": workload, "window_s": t1 - t0, "trace": tr,
               "spans": spans.between(t0, t1), "evaluate_s": hist,
               "peaks": PEAKS.get(device["kind"]), **load.layer_record()}
        metrics = {}
        for m in bench.metrics(workload, "per_layer"):
            v = bench.reader(m["name"])(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if tr is not None:
            device["busy_s"] = tr["busy_s"]
            device["window_s"] = tr["window_s"]
            out["breakdown"] = {"device_ops": [list(x) for x in tr["device_ops"]],
                                "idle_gaps": [list(x) for x in tr["idle_gaps"]]}
    else:
        e2e = load.end_to_end()
        metrics = {}
        for m in bench.metrics(workload, "end_to_end"):
            v = setup_s if m["name"] == "setup_s" else e2e[m["name"]]
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    out["metrics"] = metrics
    out["device"] = device

    load.release()
    t_check = time.perf_counter()
    checks = load.check()
    for line in getattr(load, "notes", []):
        _say(line)
    _say(f"reference comparison took {time.perf_counter() - t_check!r} s")
    out["correct"] = all(c.ok for c in checks)
    out["checks"] = {c.name: {"value": c.value, "limit": c.limit} for c in checks}
    for c in checks:
        print(f"check {c.name} {c.value!r} limit {c.limit!r} "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr, flush=True)
    return out
