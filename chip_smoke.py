#!/usr/bin/env python3
"""Bring-up check: the what-if search, the query service and the wave planner
on a TPU, driven through the entry points a user calls.

Run from the root of a checkout:

    python3 chip_smoke.py             # one chip: whatif-grid, whatif-service, planner
    python3 chip_smoke.py --chips 4   # the sharded path only (a 4-chip host)

Phases, in one process and in order:

* ``whatif-grid`` — ``search_topk(k=10)`` over ``bench_whatif``'s full
  ``EQ_SPACE`` x ``TOPK_EXTRA`` grid (967,680 configs, streamed) on a
  ``ChunkedEvaluator`` at its default chunk.  The top-10 rows and a seeded
  random sample of rows from ``evaluate`` are checked against the float64
  oracle :mod:`repro.core.hadoop.ref`: equal ``valid`` flags and costs within
  :data:`ORACLE_RTOL`.
* ``whatif-service`` — a few dozen probes, sweeps, grids and phase queries
  through ``repro.api.serve``.  Every future's result must be bit-for-bit
  equal to a sequential ``evaluate`` of the same rows (same executable).
* ``planner`` — ``ClusterEvaluator`` + ``grid_search_ev`` over 2048 cluster
  configs (``bench_cluster``'s full batch) on a 64-job Poisson trace.
  Contention-free FIFO rows must agree with the DES (``simulate_workload``)
  at rtol :data:`DES_RTOL`; the winner is re-costed through ``exact_cost``.
* ``sharded`` (``--chips 4`` only, and the only phase then) — whatif-grid's
  and the planner's top-10 on all devices, then on ``devices[:1]``: equal
  indices and ``valid`` flags, costs within ``CROSS_PROGRAM_MAX_ULP``, and
  every device holds its own shard of an evaluated chunk.

The device gate runs before any phase: JAX's first device must be a TPU, or
the script exits 1.  Nothing falls back to the CPU.  The last line of
standard output is one JSON object, printed only when every phase passed:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Times printed on earlier lines come from one smoke run, not a benchmark.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks.bench_whatif import EQ_SPACE, TOPK_EXTRA  # noqa: E402
from repro.cluster import (  # noqa: E402
    ClusterEvaluator,
    default_job_classes,
    poisson_trace,
)
from repro.compile_cache import use_compile_cache  # noqa: E402
from repro.core.hadoop.params import (  # noqa: E402
    CostFactors,
    HadoopParams,
    ProfileStats,
)
from repro.core.hadoop.ref import job_model  # noqa: E402
from repro.core.hadoop.simulator import SimConfig  # noqa: E402
from repro.search import (  # noqa: E402
    CROSS_PROGRAM_MAX_ULP,
    ChunkedEvaluator,
    apply_assignment,
    grid_search_ev,
    sample_space,
    search_topk,
    space_block,
    space_size,
    ulp_distance,
)
from repro.spec import JobSpec, PhaseBreakdown  # noqa: E402

#: The what-if grid: bench_whatif's equivalence space times its top-k extras.
WHATIF_SPACE = {**EQ_SPACE, **TOPK_EXTRA}
#: The job the what-if phases ask about (bench_whatif's base).
WHATIF_BASE = (HadoopParams(pNumNodes=16), ProfileStats(), CostFactors())

#: Relative tolerance of device costs against the float64 oracle.  The device
#: path runs in float32 (unit roundoff 2**-24, about 6e-8).  Eq. 98 is a sum
#: of non-negative per-phase products, and the longest chain of dependent
#: roundings through Eqs. 2-97 is a few dozen operations, so a row whose
#: round counts match the oracle's stays within about 50 * 6e-8 = 3e-6; the
#: TPU's divide and log2 add a few ulp each.  1e-4 leaves 30x headroom over
#: that bound, while a wrong equation is off by far more.
ORACLE_RTOL = 1e-4
#: The floor/ceil round counts of Eqs. 11-15 and 42-64: (model output key,
#: oracle section, oracle field).  A row further than ORACLE_RTOL from the
#: oracle passes only where one of these landed on the other side of its
#: edge in float32, and it is reported by name.
ROUND_COUNTS = (
    ("m_maxSerPairs", "map", "maxSerPairs"),
    ("m_maxAccPairs", "map", "maxAccPairs"),
    ("m_numSpills", "map", "numSpills"),
    ("r_numSegInShuffleFile", "reduce", "numSegInShuffleFile"),
    ("r_numShuffleFiles", "reduce", "numShuffleFiles"),
    ("r_numShuffleMerges", "reduce", "numShuffleMerges"),
    ("r_numSegmentsEvicted", "reduce", "numSegmentsEvicted"),
)

#: 2048 cluster configs: bench_cluster's full planner batch.
PLANNER_SPACE = {
    "pNumNodes": [8.0, 16.0, 32.0, 64.0, 128.0, 256.0, 512.0, 1024.0],
    "pMaxMapsPerNode": [1.0, 2.0, 4.0, 8.0],
    "pMaxRedPerNode": [1.0, 2.0, 4.0, 8.0],
    "pReduceSlowstart": [0.05, 0.8],
    "arrivalRate": [0.02, 0.05, 0.1, 0.2],
    "schedPolicy": [0.0, 1.0],                  # fifo, fair
}
#: Rows with at least this many map / reduce slots never queue a task of the
#: 64-job trace (jobs have <= 32 maps and <= 8 reducers), so the wave model
#: must reproduce the DES there.
ROOMY_MAP_SLOTS, ROOMY_RED_SLOTS = 2048.0, 512.0
#: Wave model vs DES on contention-free FIFO rows (bench_cluster's bound).
DES_RTOL = 1e-3
#: The DES without speculative backups, as bench_cluster's agreement rows.
CLEAN = SimConfig(speculative_execution=False)


class SmokeFailure(RuntimeError):
    """A phase's result disagreed with its reference."""


def _check(ok, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def _say(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def _oracle(base, assignment):
    """float64 oracle of one row: (JobModel, closed-form valid flag)."""
    p, s, c = apply_assignment(*base, assignment)
    j = job_model(p, s, c)
    f2 = p.pSortFactor ** 2
    valid = j.map.numSpills <= f2 and (
        p.pNumReducers == 0
        or (j.reduce.filesToMergeStep2 <= f2 and j.reduce.filesToMergeStep3 <= f2))
    return j, valid


def _against_oracle(phase, base, rows, cost, valid, outputs):
    """Check device rows against the oracle; returns (max rel err over the
    rows that agree, number of edge rows).  ``outputs`` (per-row model
    columns) names the round counts behind a disagreement."""
    worst, edges, n = 0.0, 0, len(cost)
    for i in range(n):
        a = {k: float(v[i]) for k, v in rows.items()}
        j, ok = _oracle(base, a)
        rel = abs(float(cost[i]) - j.totalCost) / j.totalCost if ok else 0.0
        if bool(valid[i]) == ok and rel <= ORACLE_RTOL:
            worst = max(worst, rel)
            continue
        flipped = []
        for key, part, name in ROUND_COUNTS:
            dev, ref = float(outputs[key][i]), float(getattr(getattr(j, part), name))
            if dev != ref:
                flipped.append(f"{key} {dev!r} (device) vs {ref!r} (oracle)")
        _check(flipped, f"row {a}: valid {bool(valid[i])} vs oracle {ok}, "
                        f"cost {float(cost[i])!r} vs {j.totalCost!r} "
                        f"(rel {rel!r} > {ORACLE_RTOL}) with equal round counts")
        edges += 1
        _say(phase, f"edge row {a}: {'; '.join(flipped)}; valid {bool(valid[i])} "
                    f"vs {ok}, cost rel err {rel!r}")
    return worst, edges


def phase_whatif_grid(devices, *, space=WHATIF_SPACE, n_sample=2048, seed=0):
    tag = "whatif-grid"
    ev = ChunkedEvaluator(*WHATIF_BASE, devices=devices)
    n = space_size(space)
    t0 = time.perf_counter()
    search_topk(ev, {k: v[:1] for k, v in space.items()}, k=10)
    t_compile = time.perf_counter() - t0
    t0 = time.perf_counter()
    top = search_topk(ev, space, k=10)
    t_search = time.perf_counter() - t0
    _say(tag, f"grid {n} configs, chunk {ev.chunk}, devices {ev.num_devices}, "
              f"cost dtype {ev.base_cfg['pSortMB'].dtype}")
    _say(tag, f"one smoke run, not a benchmark: cold compile + 1-row search "
              f"{t_compile!r} s; streamed top-10 {t_search!r} s = "
              f"{n / t_search!r} configs/s; valid {top.n_valid}/{top.n_evaluated}")
    _check(top.n_evaluated == n and len(top.entries) == 10,
           f"search covered {top.n_evaluated}/{n} rows, {len(top.entries)} entries")
    _check(all(e.valid and not e.exact for e in top.entries),
           "top-10 holds rows outside the closed-form domain")
    tops = {k: np.array([e.assignment[k] for e in top.entries]) for k in space}
    worst_top, edges_top = _against_oracle(      # round counts via evaluate
        tag, WHATIF_BASE, tops, [e.cost for e in top.entries],
        [e.valid for e in top.entries], ev.evaluate(tops).outputs)
    rows = sample_space(space, n_sample, seed)
    res = ev.evaluate(rows)
    worst, edges = _against_oracle(tag, WHATIF_BASE, rows, res.outputs["j_totalCost"],
                                   res.outputs["valid"] > 0, res.outputs)
    _say(tag, f"oracle (float64 ref.py): top-10 max rel err {worst_top!r}; "
              f"{n_sample} sampled rows (seed {seed}) max rel err {worst!r}, "
              f"{int((res.outputs['valid'] > 0).sum())} valid; rtol "
              f"{ORACLE_RTOL}; edge rows {edges_top + edges}")
    return top


def _service_queries(rng, n_queries):
    """(kind, overrides, phase) for a mixed query stream: probes, sweeps,
    grids (one larger than a chunk) and per-phase queries."""
    sortmb = np.array([16.0, 32.0, 64.0, 100.0, 128.0, 256.0, 512.0, 1024.0])
    factors = np.array([5.0, 10.0, 20.0, 50.0, 100.0])
    out = []
    for i in range(n_queries):
        kind = ("probe", "sweep", "grid", "phase")[i % 4]
        if kind == "probe":
            ov = {"pSortMB": float(rng.choice(sortmb)),
                  "pSortFactor": float(rng.choice(factors))}
        elif kind == "sweep":
            ov = {"pSortMB": float(rng.choice(sortmb)),
                  "pNumReducers": 2.0 ** np.arange(1, int(rng.integers(4, 9)))}
        elif kind == "grid":
            big = i == 2
            space = {
                "pSortMB": np.linspace(16.0, 1024.0, 32) if big
                else sortmb[: int(rng.integers(2, 9))],
                "pSortFactor": np.linspace(5.0, 100.0, 10) if big
                else factors[: int(rng.integers(2, 6))],
                "pNumReducers": 2.0 ** np.arange(1, 17 if big else 5),
                "pUseCombine": [0.0, 1.0],
            }
            ov = space_block(space, 0, space_size(space))
        else:
            space = {"pSortMB": sortmb[: int(rng.integers(2, 9))],
                     "pShuffleInBufPerc": [0.3, 0.5, 0.7, 0.9],
                     "pNumReducers": [4.0, 16.0, 64.0]}
            ov = space_block(space, 0, space_size(space))
        phase = str(rng.choice(PhaseBreakdown.names())) if kind == "phase" else None
        out.append((kind, ov, phase))
    return out


def phase_whatif_service(devices, *, n_queries=36, seed=0):
    import repro.api as api

    tag = "whatif-service"
    queries = _service_queries(np.random.default_rng(seed), n_queries)
    t0 = time.perf_counter()
    with api.serve(JobSpec(*WHATIF_BASE), devices=devices) as svc:
        futures = []
        for kind, ov, phase in queries:
            if kind == "probe":
                futures.append(svc.probe(ov))
            elif kind == "sweep":
                futures.append(svc.sweep("pNumReducers", ov["pNumReducers"],
                                         base={"pSortMB": ov["pSortMB"]}))
            elif kind == "grid":
                futures.append(svc.submit(ov))
            else:
                futures.append(svc.phase_query(ov, phase=phase, total_max=600.0))
        results = [f.result(timeout=900) for f in futures]
    t_all = time.perf_counter() - t0
    ev, summary = svc.evaluator, svc.summary()
    n_rows = 0
    for (kind, ov, phase), r in zip(queries, results):
        n = max(np.size(v) for v in ov.values())
        n_rows += n
        rows = {k: np.broadcast_to(np.asarray(v, dtype=np.float64), (n,))
                for k, v in ov.items()}
        if kind == "phase":
            ref = ev.report(rows)
            _check(np.array_equal(r.objective, ref.phases[phase]),
                   f"phase query ({phase}) differs from the sequential report")
            _check(np.array_equal(r.feasible, (np.asarray(ref.valid) > 0)
                                  & (np.asarray(ref.total_cost) <= 600.0)),
                   "phase query feasibility differs from the sequential report")
            continue
        ref = ev.evaluate(rows)
        for k, v in ref.outputs.items():
            _check(np.array_equal(r.outputs[k], v),
                   f"{kind} query output {k} differs from sequential evaluate")
        _check(np.array_equal(r.total_cost[~r.exact], ref.total_cost[~r.exact]),
               f"{kind} query cost differs from sequential evaluate")
    _say(tag, f"{len(results)} queries, {n_rows} rows, all futures resolved; "
              f"bit-for-bit equal to sequential evaluate; chunks "
              f"{summary['chunks']} ({summary['shared_chunks']} shared), "
              f"exact re-costs {summary['exact_rows']}")
    _say(tag, f"one smoke run, not a benchmark: {t_all!r} s incl. compiles; "
              f"latency p50 {summary['latency_p50_s']!r} s, "
              f"p99 {summary['latency_p99_s']!r} s")


def _planner(devices, n_jobs, seed, chunk):
    trace = poisson_trace(default_job_classes(), n_jobs, rate=1.0, seed=seed)
    return ClusterEvaluator(traces=[trace], sim=CLEAN, chunk=chunk,
                            devices=devices)


def phase_planner(devices, *, space=PLANNER_SPACE, n_jobs=64, seed=3,
                  n_agree=4):
    tag = "planner"
    n = space_size(space)
    ev = _planner(devices, n_jobs, seed, n)
    cols = space_block(space, 0, n)
    t0 = time.perf_counter()
    full = ev.evaluate(cols)
    t_cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    best = grid_search_ev(ev, space)
    t_search = time.perf_counter() - t0
    n_valid = int((full.outputs["valid"] > 0).sum())
    _say(tag, f"{n} scenarios x {n_jobs} jobs, devices {ev.num_devices}; "
              f"valid (converged) {n_valid}/{n}")
    _say(tag, f"one smoke run, not a benchmark: cold evaluate (incl. compile) "
              f"{t_cold!r} s; grid_search_ev {t_search!r} s = "
              f"{n / t_search!r} scenarios/s")
    _check(n_valid == n, f"{n - n_valid} scenarios did not converge")
    roomy = np.flatnonzero(
        (cols["schedPolicy"] == 0)
        & (cols["pNumNodes"] * cols["pMaxMapsPerNode"] >= ROOMY_MAP_SLOTS)
        & (cols["pNumNodes"] * cols["pMaxRedPerNode"] >= ROOMY_RED_SLOTS))
    _check(roomy.size >= n_agree, "no contention-free FIFO rows in the grid")
    picks = np.random.default_rng(seed).choice(roomy, n_agree, replace=False)
    worst = 0.0
    for i in picks:
        a = {k: float(v[i]) for k, v in cols.items()}
        des = ev.exact_cost(a)
        rel = abs(float(full.total_cost[i]) - des) / des
        _check(rel <= DES_RTOL, f"contention-free FIFO row {a}: wave "
                                f"{float(full.total_cost[i])!r} vs DES {des!r}")
        worst = max(worst, rel)
    exact = ev.exact_cost(best.best_assignment)
    _check(np.isfinite(best.best_cost) and np.isfinite(exact), "no finite winner")
    _say(tag, f"contention-free FIFO rows vs DES: {n_agree} rows, max rel err "
              f"{worst!r} (rtol {DES_RTOL})")
    _say(tag, f"winner {best.best_assignment}: wave p95 {best.best_cost!r} s, "
              f"DES re-cost {exact!r} s")


def _same_topk(tag, what, a, b):
    """Equal indices and valid flags, float32 costs within the ulp bound."""
    _check([e.index for e in a.entries] == [e.index for e in b.entries],
           f"{what}: top-k indices differ across device counts")
    _check([e.valid for e in a.entries] == [e.valid for e in b.entries],
           f"{what}: top-k valid flags differ across device counts")
    ulps = ulp_distance(np.float32([e.cost for e in a.entries]),
                        np.float32([e.cost for e in b.entries]))
    _check(ulps.max() <= CROSS_PROGRAM_MAX_ULP,
           f"{what}: top-k costs {int(ulps.max())} ulp apart")
    _say(tag, f"{what}: top-{len(a.entries)} indices equal, max "
              f"{int(ulps.max())} ulp (bound {CROSS_PROGRAM_MAX_ULP})")


def _check_shards(tag, ev, space):
    """Each device holds its own slice of one evaluated chunk."""
    rows = space_block(space, 0, min(ev.chunk, space_size(space)))
    batched, static, n = ev._split(rows)          # the evaluator's own chunk
    cols, _ = ev._pad(batched, 0, n)
    cost = ev._eval_fn(cols, static)["j_totalCost"]
    host = np.asarray(cost)
    per = ev.chunk // ev.num_devices
    shards = sorted(cost.addressable_shards, key=lambda s: s.index[0].start)
    _check([s.device for s in shards] == list(ev._mesh.devices.flat),
           f"shards on {[s.device for s in shards]}")
    for i, s in enumerate(shards):
        _check(s.data.shape == (per,)
               and np.array_equal(np.asarray(s.data), host[i * per:(i + 1) * per]),
               f"shard {i} on {s.device} does not hold rows {i * per}..")
    _say(tag, f"{len(shards)} devices each hold {per} rows of a {ev.chunk}-row "
              "chunk")


def phase_sharded(devices, *, space=WHATIF_SPACE, planner_space=PLANNER_SPACE,
                  n_jobs=64, seed=3):
    tag = "sharded"
    n_p = space_size(planner_space)
    tops = {}
    for label, devs in (("all", list(devices)), ("one", list(devices)[:1])):
        ev = ChunkedEvaluator(*WHATIF_BASE, devices=devs)
        if label == "all":
            _check_shards(tag, ev, space)
        t0 = time.perf_counter()
        tops["whatif", label] = search_topk(ev, space, k=10)
        t_w = time.perf_counter() - t0
        t0 = time.perf_counter()
        tops["planner", label] = search_topk(
            _planner(devs, n_jobs, seed, n_p), planner_space, k=10)
        t_p = time.perf_counter() - t0
        _say(tag, f"{len(devs)} device(s): whatif top-10 over "
                  f"{space_size(space)} configs {t_w!r} s, planner top-10 over "
                  f"{n_p} scenarios {t_p!r} s (one smoke run, incl. compiles)")
    _same_topk(tag, "whatif-grid", tops["whatif", "all"], tops["whatif", "one"])
    _same_topk(tag, "planner", tops["planner", "all"], tops["planner", "one"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded phase, across 4 chips")
    args = ap.parse_args(argv)

    devs = jax.devices()
    d0 = devs[0]
    print(f"device: platform={d0.platform} kind={d0.device_kind!r} "
          f"count={len(devs)} jax={jax.__version__} "
          f"jax_enable_x64={jax.config.jax_enable_x64}", flush=True)
    if d0.platform != "tpu":
        print(f"device gate: JAX found no TPU (first device is "
              f"{d0.platform}); nothing was run", file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"device gate: --chips {args.chips} needs {args.chips} "
              f"devices, JAX found {len(devs)}", file=sys.stderr)
        return 1
    print(f"compile cache: {use_compile_cache(ROOT)}", flush=True)

    if args.chips == 4:
        phases = [("sharded", lambda: phase_sharded(devs[:4]))]
    else:
        one = devs[:1]
        phases = [("whatif-grid", lambda: phase_whatif_grid(one)),
                  ("whatif-service", lambda: phase_whatif_service(one)),
                  ("planner", lambda: phase_planner(one))]
    failed = []
    for name, run in phases:
        t0 = time.perf_counter()
        try:
            run()
        except Exception:
            traceback.print_exc()
            failed.append(name)
        status = "FAILED" if failed and failed[-1] == name else "OK"
        _say(name, f"{status} in {time.perf_counter() - t0!r} s")
    if failed:
        print(f"failed phases: {', '.join(failed)}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind, "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
