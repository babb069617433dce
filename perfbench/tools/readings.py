#!/usr/bin/env python3
"""Readings of a cell's compared numbers on several seeds in one process:
the program's, or with ``--control`` the control's (``harness.controls``),
at the cell's own size with a short window.

    python3 perfbench/tools/readings.py --workload whatif-terasort-grid \\
        --seeds 101,102,103 --seconds 5 --control

Prints one JSON line per seed: the seed, ``correct`` and every number with
its limit.  A control must come out not correct on every seed.
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
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)

    from perfbench.harness.bench import Bench
    from perfbench.harness.controls import control_kw
    from perfbench.harness.device import gate
    from perfbench.harness.runner import run_cell, use_compile_cache

    bench = Bench(ROOT)
    cell = bench.cell(args.workload)
    devs = gate(int(cell["chips"]))
    use_compile_cache(ROOT)
    kw = control_kw(bench.traffic(cell["traffic"])["load"]) if args.control else {}
    for seed in (int(s) for s in args.seeds.split(",")):
        try:
            out = run_cell(bench, args.workload, seed=seed, seconds=args.seconds,
                           trace=False, devs=devs, t_start=time.perf_counter(), **kw)
            line = {"seed": seed, "control": args.control, "correct": out["correct"],
                    "checks": out["checks"]}
        except Exception as e:          # noqa: BLE001 - a crashed control has failed
            line = {"seed": seed, "control": args.control, "correct": False,
                    "error": repr(e)}
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
