"""Benchmark driver: ``PYTHONPATH=src python -m benchmarks.run [--quick]``.

One module per paper aspect:

  E4  bench_sim_vs_analytic  analytic job cost vs task-scheduler simulation
  E5  bench_whatif           what-if engine throughput (vmap vs python)
  E6  bench_tuner            tuner vs exhaustive optimum
  E7  bench_mr_fit           fitted cost factors -> prediction error
  E8  bench_roofline         40-cell dry-run roofline table
  E9  bench_tpu_model        TPU analytical model vs compiled dry-run
  E11 bench_kernels          Pallas kernels vs jnp oracles
  E12 bench_service          async what-if service vs per-query baseline
  E13 bench_cluster          vectorized capacity planner vs per-scenario DES
  E14 bench_obs              observability overhead (bit-for-bit + < 5%)

Markdown reports land in artifacts/bench/, machine-readable metrics in
artifacts/bench/BENCH_results.json (one entry per module, merged across
invocations).
"""

from __future__ import annotations

import argparse
import time
import traceback

MODULES = [
    ("E4 sim_vs_analytic", "benchmarks.bench_sim_vs_analytic"),
    ("E5 whatif", "benchmarks.bench_whatif"),
    ("E6 tuner", "benchmarks.bench_tuner"),
    ("E7 mr_fit", "benchmarks.bench_mr_fit"),
    ("E8 roofline", "benchmarks.bench_roofline"),
    ("E9 tpu_model", "benchmarks.bench_tpu_model"),
    ("E11 kernels", "benchmarks.bench_kernels"),
    ("E12 service", "benchmarks.bench_service"),
    ("E13 cluster", "benchmarks.bench_cluster"),
    ("E14 obs", "benchmarks.bench_obs"),
    ("E15 cloud", "benchmarks.bench_cloud"),
    ("serving", "benchmarks.bench_serving"),
    ("analysis gate", "benchmarks.bench_analysis"),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default="", help="substring filter")
    args = ap.parse_args()

    from repro.compile_cache import use_compile_cache

    from .common import RESULTS_NAME, ROOT, report

    use_compile_cache(ROOT)

    failures = 0
    for label, modname in MODULES:
        if args.only and args.only not in modname and args.only not in label:
            continue
        t0 = time.time()
        print(f"\n===== {label} ({modname}) =====", flush=True)
        try:
            mod = __import__(modname, fromlist=["run"])
            lines = mod.run(quick=args.quick)
            print("\n".join(lines))
            wall = time.time() - t0
            print(f"[done in {wall:.1f}s]")
            report(modname.rsplit(".", 1)[-1], wall_s=wall, ok=1)
        except Exception:
            failures += 1
            report(modname.rsplit(".", 1)[-1], wall_s=time.time() - t0, ok=0)
            print(f"[FAILED]\n{traceback.format_exc()[-3000:]}")
    if failures:
        raise SystemExit(f"{failures} benchmark modules failed")
    print(f"\nAll benchmarks complete; reports + {RESULTS_NAME} in "
          "artifacts/bench/")


if __name__ == "__main__":
    main()
