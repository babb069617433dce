#!/usr/bin/env python3
"""Find the highest query rate the service sustains: one set-up, then one
window per offered rate, in one process on the chip.

    python3 perfbench/tools/rate_sweep.py --workload whatif-terasort-service \\
        --rates 20,40,80,160 --seconds 15 --seed 7

Per rate it prints the queries due, the backlog at the window's close, the
time the last query took to resolve after the close, and p50 / p95 latency.
A rate is sustained where the backlog does not grow: at most 2% of the
window's queries are open at its close and the last resolves within a
second of it.  The sweep stops at the first rate not sustained.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="whatif-terasort-service")
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--write", action="store_true",
                    help="set the traffic file's rate_qps to 4/5 of the highest rate sustained")
    args = ap.parse_args(argv)

    from perfbench.loads.service_open import Load
    from perfbench.harness.bench import Bench
    from perfbench.harness.device import gate
    from perfbench.harness.runner import use_compile_cache
    from perfbench.harness.window import Spans

    bench = Bench(ROOT)
    cell = bench.cell(args.workload)
    gate(int(cell["chips"]))
    use_compile_cache(ROOT)
    d = Load(bench.config(cell["config"]), bench.traffic(cell["traffic"]), args.seed,
               Spans())
    d.setup()
    sustained = []
    for rate in (float(r) for r in args.rates.split(",")):
        d.traffic["rate_qps"] = rate
        d.run(args.seconds)
        t_close = time.perf_counter()
        d.drain()
        e2e = d.end_to_end()
        last = max(x for x in d.done if x is not None) - t_close
        print(f"rate {rate} q/s: {len(d.queries)} queries, backlog at close {d.backlog}, "
              f"last resolved {last!r} s after close, p50 {e2e['query_p50_ms']!r} ms, "
              f"p95 {e2e['query_p95_ms']!r} ms, {d.window_notes}", flush=True)
        if last > 1.0 or d.backlog > 0.02 * len(d.queries):
            break                       # the queue grows: higher rates only add backlog
        sustained.append(rate)
    d.release()
    best = max(sustained, default=None)
    print(f"highest rate sustained: {best} q/s", flush=True)
    if args.write and best:
        path = bench.traffic_dir / f"{cell['traffic']}.json"
        mix = json.loads(path.read_text())
        mix["rate_qps"] = round(0.8 * best)
        path.write_text(json.dumps(mix, indent=1) + "\n")
        print(f"rate_qps set to {mix['rate_qps']} in {path.name}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
