"""E14 — observability overhead: instrumentation must be free when off
and cheap when on.

Two claims, asserted in ``--smoke`` (CI) mode rather than eyeballed:

1. **Bit-for-bit** — running :class:`repro.search.ChunkedEvaluator`
   under ``repro.obs.observe()`` returns *exactly* the numbers an
   uninstrumented run returns, for every output column.  Instrumentation
   reads the computation; it never participates in it.
2. **Overhead** — with tracing ON, the min-of-N wall time of a warmed
   evaluate sweep stays within 5% of the uninstrumented min-of-N (the
   hot path only pays guarded counter bumps and span dict appends; no
   allocation happens inside jitted code either way).

The report also shows what a run *records*: the ambient registry
snapshot (chunks, rows, padded rows, bytes fetched) and the trace event
count, as a sanity check that the instrumentation actually fires.

Run:  PYTHONPATH=src python -m benchmarks.bench_obs [--smoke] [--quick]
"""

from __future__ import annotations

import time

import numpy as np

from repro.core.hadoop.params import CostFactors, HadoopParams, MiB, ProfileStats
from repro.obs import observe
from repro.search import ChunkedEvaluator

from .common import report, table, write_md


def _sweep(n_rows: int) -> dict[str, np.ndarray]:
    rng = np.random.default_rng(7)
    return {
        "pSortMB": rng.choice([16.0, 25.0, 50.0, 100.0, 200.0], n_rows),
        "pSortFactor": rng.choice([5.0, 10.0, 25.0, 50.0], n_rows),
        "pNumReducers": 2.0 ** rng.integers(1, 7, n_rows),
    }


def _min_of(n: int, fn) -> float:
    best = float("inf")
    for _ in range(n):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def run(quick: bool = False, smoke: bool = False) -> list[str]:
    small = quick or smoke
    n_rows = 1 << 10 if small else 1 << 13
    reps = 5 if small else 10
    hp = HadoopParams(pNumNodes=8, pNumMappers=64, pNumReducers=16,
                      pSplitSize=128 * MiB)
    ev = ChunkedEvaluator(hp, ProfileStats(sMapSizeSel=0.8), CostFactors(),
                          chunk=1 << 8)
    rows = _sweep(n_rows)
    ev.evaluate(rows)                      # warm the compiled executable

    # ---- claim 1: observe() does not perturb the numbers ----
    plain = ev.evaluate(rows)
    with observe() as ob:
        traced = ev.evaluate(rows)
    assert np.array_equal(plain.total_cost, traced.total_cost), \
        "observe() changed evaluator results"
    for k in plain.outputs:
        assert np.array_equal(plain.outputs[k], traced.outputs[k]), k
    snap = ob.registry.snapshot()
    n_events = len(ob.tracer.events())
    assert snap.get("evaluator.rows") == n_rows, snap
    assert n_events > 0, "tracing recorded no events"

    # ---- claim 2: overhead within 5% (min-of-N, warmed) ----
    t_off = _min_of(reps, lambda: ev.evaluate(rows))

    def traced_run():
        with observe():
            ev.evaluate(rows)

    t_on = _min_of(reps, traced_run)
    overhead = t_on / max(t_off, 1e-12) - 1.0
    if smoke:
        assert overhead < 0.05, (
            f"instrumentation overhead {overhead * 100:.1f}% >= 5%"
        )

    interesting = {k: v for k, v in snap.items()
                   if not isinstance(v, dict)}
    lines = [
        f"workload: {n_rows} rows through ChunkedEvaluator(chunk={ev.chunk}),"
        f" min-of-{reps}{', smoke' if smoke else ', quick' if quick else ''}",
        "",
        "equivalence: instrumented run **bit-for-bit identical** to the "
        "uninstrumented run, every output column (asserted)",
        f"recorded: {n_events} trace events; registry "
        + ", ".join(f"{k}={v:g}" for k, v in sorted(interesting.items())),
        "",
    ]
    lines += table(
        ["mode", "min wall s", "rows/s"],
        [["observability off (default)", t_off, n_rows / t_off],
         ["observe() tracing on", t_on, n_rows / t_on]],
    )
    lines += ["", f"**overhead: {overhead * 100:+.2f}%** wall time with "
                  "tracing on (gate: < 5% in smoke mode)"]
    report("bench_obs", overhead_pct=overhead * 100, trace_events=n_events,
           rows=n_rows)
    write_md("obs.md", "Observability overhead", lines)
    return lines


if __name__ == "__main__":
    from .common import bench_main

    bench_main(run)
