#!/usr/bin/env python3
"""A traced run of one cell with the program's own spans live, on the chip.

    python3 perfbench/tools/program_spans.py --workload whatif-terasort-grid \\
        --seed 7 --seconds 20 [--sample <file>]

Like ``run.py --trace 1``, but with ``repro.obs``'s tracer installed: each of
the program's spans lands in the profiler's trace as ``repro:<name>`` and
takes its share of the idle attribution (``harness.program_trace``), and the
program's histograms and counters reach the readers as ``run["program"]``.
Prints one JSON line: the cell's per-layer metrics, the program-span metrics
of ``PROGRAM_METRICS``, the end-to-end metrics of this traced window, the
number of program spans in the profiler's trace, the idle breakdown and the
device.  ``--sample`` keeps a few hundred events of the window
(``runner._keep_sample``), program spans included.  No reference
comparison is made.
"""

from __future__ import annotations

import argparse
import importlib
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

#: the readers of ``run["program"]`` (``perfbench/metrics/<name>.py``) per cell
PROGRAM_METRICS = {
    "whatif-terasort-grid": ["topk.prepare_ms", "topk.dispatch_ms", "topk.fetch_ms"],
    "whatif-terasort-service": ["service.queue_wait_ms", "evaluate.fetch_ms",
                                "service.d2h_kib_per_query"],
    "planner-fb2009-hour": ["planner.build_ms", "rollout.lane_use"],
}


def traced_run(bench, workload: str, *, seed: int, seconds: float, devs,
               sample: Path | None = None) -> dict:
    from perfbench.harness.device import PEAKS, describe
    from perfbench.harness.program_trace import (PROGRAM_PREFIX, program_record, read_events,
                                                 reduce_events)
    from perfbench.harness.runner import OUT_DIR, _keep_sample, _profiled
    from perfbench.harness.window import Spans
    from repro.obs import observe

    cell = bench.cell(workload)
    traffic = bench.traffic(cell["traffic"])
    spans = Spans(annotate=True)
    load = importlib.import_module(f"perfbench.loads.{traffic['load']}").Load(
        bench.config(cell["config"]), traffic, seed, spans)
    load.setup()
    seconds = min(seconds, float(traffic.get("trace_seconds", seconds)))
    logdir = OUT_DIR / f"spans-{workload}"
    with observe() as ob:
        spans.records.clear()
        with _profiled(True, logdir):
            t0 = time.perf_counter()
            with spans.span("window"):
                load.run(seconds)
            t1 = time.perf_counter()
        if hasattr(load, "drain"):
            load.drain()
    events = read_events(str(logdir))
    tr = reduce_events(events)
    if sample is not None:
        _keep_sample(events, sample)
    device = describe(devs)
    run = {"cell": workload, "window_s": t1 - t0, "trace": tr,
           "spans": spans.between(t0, t1),
           "evaluate_s": ob.registry.histogram("evaluator.evaluate_s").samples(),
           "peaks": PEAKS.get(device["kind"]), "program": program_record(ob.registry),
           **load.layer_record()}
    names = ([m["name"] for m in bench.metrics(workload, "per_layer")]
             + PROGRAM_METRICS[workload])
    metrics = {n: bench.reader(n)(run) for n in names}
    e2e = load.end_to_end()
    load.release()
    out = {"cell": workload, "seed": seed, "window_s": t1 - t0,
           "metrics": {n: v for n, v in metrics.items() if v is not None},
           "end_to_end_traced": e2e,
           "program_events": sum(1 for e in events if e[2].startswith(PROGRAM_PREFIX)),
           "device": device}
    if tr is not None:
        device["busy_s"], device["window_s"] = tr["busy_s"], tr["window_s"]
        out["breakdown"] = {"device_ops": [list(x) for x in tr["device_ops"]],
                            "idle_gaps": [list(x) for x in tr["idle_gaps"]]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True, choices=sorted(PROGRAM_METRICS))
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--sample", type=Path, default=None)
    args = ap.parse_args(argv)

    from perfbench.harness.bench import Bench
    from perfbench.harness.device import gate
    from perfbench.harness.runner import use_compile_cache

    bench = Bench(ROOT)
    devs = gate(int(bench.cell(args.workload)["chips"]))
    use_compile_cache(ROOT)
    print(json.dumps(traced_run(bench, args.workload, seed=args.seed, seconds=args.seconds,
                                devs=devs, sample=args.sample)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
