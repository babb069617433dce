#!/usr/bin/env python3
"""Run cells of the benchmark as the check runs them, one process per run,
and summarise: each run's metrics and checks, and per cell and metric the
median and the spread (quartile distance over the median) of each set.

    python3 perfbench/tools/runs.py --plan whatif-terasort-grid:11,12,13 \\
        --plan planner-fb2009-hour:21,22 --seconds 20 --trace 0 --out runs.jsonl

A plan ``cell:s1,s2,...`` runs that cell once per seed, in order; ``--sets 2``
runs the whole plan twice with the same seeds.  This process never touches
JAX, so each run has the chip to itself.
"""

from __future__ import annotations

import argparse
import json
import statistics
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from perfbench.harness.stats import spread  # noqa: E402  (no JAX: the runs get the chip)


def one(cell: str, seed: int, seconds: float, trace: int) -> dict:
    cmd = [sys.executable, "perfbench/run.py", "--workload", cell, "--seed", str(seed),
           "--seconds", str(seconds), "--trace", str(trace)]
    t0 = time.perf_counter()
    p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
    wall = time.perf_counter() - t0
    lines = p.stdout.strip().splitlines()
    try:
        res = json.loads(lines[-1]) if lines else None
    except json.JSONDecodeError:
        res = None
    return {"cell": cell, "seed": seed, "trace": trace, "rc": p.returncode, "wall_s": wall,
            "result": res, "stderr_tail": p.stderr[-3000:]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--plan", action="append", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--sets", type=int, default=1)
    ap.add_argument("--out", default=".perfbench_out/runs.jsonl")
    args = ap.parse_args(argv)
    out = ROOT / args.out
    out.parent.mkdir(parents=True, exist_ok=True)
    recs = []
    with out.open("a") as fh:
        for s in range(args.sets):
            for plan in args.plan:
                cell, seeds = plan.split(":")
                for seed in (int(x) for x in seeds.split(",")):
                    r = one(cell, seed, args.seconds, args.trace)
                    r["set"] = s
                    recs.append(r)
                    fh.write(json.dumps(r) + "\n")
                    fh.flush()
                    res = r["result"] or {}
                    m = {k: v["value"] for k, v in res.get("metrics", {}).items()}
                    bad = {k: v for k, v in res.get("checks", {}).items()
                           if v["value"] > v["limit"]}
                    print(f"{cell} seed {seed} set {s} rc {r['rc']} wall {r['wall_s']:.1f}s "
                          f"correct {res.get('correct')} metrics {m} "
                          f"failed_checks {bad}", flush=True)
                    if r["rc"] != 0 or not res:
                        print(r["stderr_tail"][-1500:], flush=True)
    cells = sorted({r["cell"] for r in recs})
    for cell in cells:
        for s in range(args.sets):
            rs = [r for r in recs if r["cell"] == cell and r["set"] == s and r["result"]]
            names = sorted({k for r in rs for k in r["result"]["metrics"]})
            for k in names:
                vals = [r["result"]["metrics"][k]["value"] for r in rs
                        if k in r["result"]["metrics"]]
                print(f"SUMMARY {cell} set {s} {k}: n {len(vals)} median "
                      f"{statistics.median(vals)!r} spread "
                      f"{spread(vals) if len(vals) > 1 else float('nan')!r} values {vals}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
