#!/usr/bin/env python3
"""The benchmark's entry point.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine that holds the chips the cell
asks for.  The cell, its configuration, its traffic mix and its metrics are
found by name through ``BENCHMARK.json``.  The last line of standard output
is one JSON object (``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, with ``--trace 1`` also ``breakdown``, and last the numbers
compared with their limits under ``checks``); the same numbers end standard
error.  Without a TPU, or with fewer chips than the cell asks for, it exits
with code 2 and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()       # set-up is timed from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
# libtpu would otherwise write its logs to a fixed directory outside the checkout
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from perfbench.harness.bench import Bench
    from perfbench.harness.device import DeviceGateError, gate
    from perfbench.harness.runner import run_cell, use_compile_cache

    bench = Bench(ROOT)
    cell = bench.cell(args.workload)
    try:
        devs = gate(int(cell["chips"]))
    except DeviceGateError as e:
        print(f"device gate: {e}", file=sys.stderr)
        return 2
    print(f"compile cache: {use_compile_cache(ROOT)}", file=sys.stderr, flush=True)
    out = run_cell(bench, args.workload, seed=args.seed, seconds=args.seconds,
                   trace=bool(args.trace), devs=devs, t_start=T_START)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
