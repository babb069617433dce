"""Shared helpers for the benchmark harness.

Besides the markdown report writers, this module is the benchmarks'
machine-readable sink: :func:`report` records per-benchmark metrics into
:class:`repro.obs.MetricsRegistry` instances, and :func:`write_results`
persists every registry as one ``artifacts/bench/BENCH_results.json``
(benchmark name -> metrics snapshot), merging with whatever is already on
disk so successive CI steps (each a separate process) accumulate into a
single artifact.

:func:`bench_main` is the standard ``__main__`` for a benchmark module: it
exposes the module's ``run(quick=..., smoke=...)`` flags plus a uniform
``--trace OUT.json`` flag that wraps the run in :func:`repro.obs.observe`
and writes a Perfetto-loadable Chrome trace.
"""

from __future__ import annotations

import contextlib
import inspect
import json
import os
import time
from pathlib import Path

from repro.obs import MetricsRegistry

#: root of the checkout (holds the compile cache, see repro.compile_cache)
ROOT = Path(__file__).resolve().parents[1]
ART = "artifacts/bench"
RESULTS_NAME = "BENCH_results.json"

_registries: dict[str, MetricsRegistry] = {}


def registry(bench: str) -> MetricsRegistry:
    """The named benchmark's metrics registry (created on first use)."""
    return _registries.setdefault(bench, MetricsRegistry())


def report(bench: str, **metrics) -> MetricsRegistry:
    """Record scalar results for one benchmark and persist immediately
    (so a later module's crash cannot lose an earlier module's numbers).
    Values become gauges; pass a ``repro.obs`` snapshot dict via
    :func:`merge_snapshot` for nested histogram summaries."""
    reg = registry(bench)
    for k, v in metrics.items():
        reg.gauge(k).set(float(v))
    write_results()
    return reg


def merge_snapshot(bench: str, snapshot: dict) -> None:
    """Fold a ``MetricsRegistry.snapshot()`` (e.g. the ambient registry of
    an ``observe()`` run) into a benchmark's results entry."""
    reg = registry(bench)
    for k, v in snapshot.items():
        if isinstance(v, dict):          # histogram summary: keep the p50/p99
            for kk, vv in v.items():
                reg.gauge(f"{k}.{kk}").set(float(vv))
        else:
            reg.gauge(k).set(float(v))
    write_results()


def write_results(path: str | None = None) -> str:
    """Write every reported registry to ``BENCH_results.json``, merged with
    the file's current content (separate CI steps accumulate)."""
    os.makedirs(ART, exist_ok=True)
    path = path or os.path.join(ART, RESULTS_NAME)
    existing: dict = {}
    if os.path.exists(path):
        try:
            with open(path) as f:
                existing = json.load(f)
        except (OSError, ValueError):
            existing = {}
    for name, reg in _registries.items():
        merged = existing.get(name, {})
        if not isinstance(merged, dict):
            merged = {}
        merged.update(reg.snapshot())
        existing[name] = merged
    with open(path, "w") as f:
        json.dump(existing, f, indent=2, sort_keys=True)
        f.write("\n")
    return path


def bench_main(run_fn) -> None:
    """Uniform benchmark ``__main__``: module flags + ``--trace OUT.json``.

    Builds an argparse CLI from ``run_fn``'s signature (``--quick`` /
    ``--smoke`` when the corresponding parameters exist), runs the module,
    prints its report lines, and persists ``BENCH_results.json``.  With
    ``--trace``, the run executes inside :func:`repro.obs.observe`; the
    trace lands at the given path and the ambient metrics snapshot is
    folded into the benchmark's results entry.
    """
    import argparse

    from repro.compile_cache import use_compile_cache
    from repro.obs import observe

    mod = inspect.getmodule(run_fn)
    name = (mod.__name__ if mod else "bench").rsplit(".", 1)[-1]
    if name == "__main__" and getattr(mod, "__file__", None):
        name = os.path.splitext(os.path.basename(mod.__file__))[0]
    params = inspect.signature(run_fn).parameters
    ap = argparse.ArgumentParser(description=(mod.__doc__ or "").strip()
                                 or None)
    if "quick" in params:
        ap.add_argument("--quick", action="store_true")
    if "smoke" in params:
        ap.add_argument("--smoke", action="store_true",
                        help="CI mode: small inputs + hard assertions")
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="record this run's spans/metrics and write a "
                         "Perfetto-loadable Chrome trace")
    args = ap.parse_args()
    kw = {k: getattr(args, k) for k in ("quick", "smoke") if k in params}
    use_compile_cache(ROOT)

    t0 = time.perf_counter()
    cm = observe(args.trace) if args.trace else contextlib.nullcontext()
    with cm as ob:
        lines = run_fn(**kw)
    print("\n".join(lines))
    report(name, wall_s=time.perf_counter() - t0)
    if ob is not None:
        merge_snapshot(name, ob.registry.snapshot())
        print(f"[trace written to {args.trace}; open at https://ui.perfetto.dev]")


def write_md(name: str, title: str, lines: list[str]) -> str:
    os.makedirs(ART, exist_ok=True)
    path = os.path.join(ART, name)
    with open(path, "w") as f:
        f.write(f"# {title}\n\n")
        f.write("\n".join(lines) + "\n")
    return path


def table(headers: list[str], rows: list[list]) -> list[str]:
    out = ["| " + " | ".join(headers) + " |",
           "|" + "|".join("---" for _ in headers) + "|"]
    for r in rows:
        out.append("| " + " | ".join(
            f"{v:.4g}" if isinstance(v, float) else str(v) for v in r
        ) + " |")
    return out


class timer:
    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *a):
        self.s = time.perf_counter() - self.t0
