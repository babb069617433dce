"""E5 — what-if engine throughput at production grid scale.

Three claims, in the order the search subsystem makes them:

1. **Equivalence** — a >= 10^5-config grid evaluated through the chunked,
   device-sharded path (:class:`repro.search.ChunkedEvaluator`) agrees with
   the seed's unchunked single-device ``jit(vmap(model))`` call (padding
   rows masked out): equal ``valid`` flags and costs within
   ``CROSS_PROGRAM_MAX_ULP`` (two executables are not bitwise equal).
   Asserted, not eyeballed.
2. **Scale** — the streaming on-device top-k path sweeps a ~10^6-config
   Cartesian space in bounded memory with ONE compile, reporting configs/s.
3. **Context** — the pure-Python oracle rate, to show why the vectorized
   formulation exists (the paper's tuning loop needs 10^4-10^6 evals).
"""

from __future__ import annotations

import numpy as np

from repro.core.hadoop.params import CostFactors, HadoopParams, MiB, ProfileStats
from repro.core.hadoop.ref import job_model
from repro.search import (
    CROSS_PROGRAM_MAX_ULP,
    ChunkedEvaluator,
    evaluate_unchunked,
    search_topk,
    space_block,
    space_size,
    ulp_distance,
)
from .common import table, timer, write_md

# ~1.2e5 configs: the chunked-vs-unchunked equivalence grid (full mode).
EQ_SPACE = {
    "pSortMB": [16.0, 32.0, 64.0, 100.0, 128.0, 256.0, 512.0, 1024.0],
    "pSortFactor": [5.0, 10.0, 20.0, 50.0, 100.0],
    "pNumReducers": [2.0, 4.0, 8.0, 16.0, 32.0, 64.0, 128.0],
    "pShuffleInBufPerc": [0.3, 0.5, 0.7, 0.9],
    "pIsIntermCompressed": [0.0, 1.0],
    "pUseCombine": [0.0, 1.0],
    "pNumMappers": [32.0, 64.0, 128.0],
    "pSortRecPerc": [0.01, 0.05, 0.15],
    "pSplitSize": [64.0 * MiB, 128.0 * MiB, 256.0 * MiB],
}

# x8 more: the ~10^6-config streaming top-k space (never materialized).
TOPK_EXTRA = {
    "pInMemMergeThr": [100.0, 1000.0],
    "pShuffleMergePerc": [0.5, 0.66],
    "pReducerInBufPerc": [0.0, 0.35],
}


def _quick_space(space, n_axes=5, n_vals=3):
    return {k: v[:n_vals] for k, v in list(space.items())[:n_axes]}


def run(quick: bool = False) -> list[str]:
    hp, st, cf = HadoopParams(pNumNodes=16), ProfileStats(), CostFactors()
    eq_space = _quick_space(EQ_SPACE) if quick else EQ_SPACE
    topk_space = dict(eq_space, **({} if quick else TOPK_EXTRA))
    lines: list[str] = []

    # ---- 1: equivalence, chunked+sharded vs unchunked single-device ----
    n_eq = space_size(eq_space)
    ev = ChunkedEvaluator(hp, st, cf, chunk=1 << 13)
    cols = space_block(eq_space, 0, n_eq)

    with timer() as t_un:
        ref = evaluate_unchunked(ev.base_cfg, cols)
    ref_cost = np.where(ref["valid"] > 0, ref["j_totalCost"], np.inf)

    with timer() as t_ch:
        res = ev.evaluate(cols)

    assert np.array_equal(res.outputs["valid"], ref["valid"]), \
        "chunked/sharded path disagrees with unchunked reference on valid"
    max_ulp = int(ulp_distance(res.total_cost, ref_cost).max())
    assert max_ulp <= CROSS_PROGRAM_MAX_ULP, \
        f"chunked/sharded path {max_ulp} ulp from unchunked reference"
    lines += [
        f"equivalence grid: {n_eq} configs "
        f"({'quick mode, ' if quick else ''}devices={ev.num_devices}, "
        f"chunk={ev.chunk})",
        f"chunked+sharded vs unchunked single-device: equal valid flags, "
        f"**max {max_ulp} ulp** (bound {CROSS_PROGRAM_MAX_ULP}; "
        f"{int(np.isfinite(ref_cost).sum())} valid configs)",
        f"compiles used by the chunked path: {ev.eval_cache_size()}",
        "",
    ]

    # ---- 2: streaming top-k throughput at ~10^6 configs ----
    n_topk = space_size(topk_space)
    # warm the top-k executable on a tiny same-keys sub-space
    search_topk(ev, {k: v[:1] for k, v in topk_space.items()}, k=10)
    with timer() as t_tk:
        top = search_topk(ev, topk_space, k=10)
    rate_topk = n_topk / t_tk.s

    best = top.best()
    lines += [
        f"streaming top-10 over {n_topk} configs "
        f"(grid never materialized, {ev.topk_cache_size()} compile): "
        f"{t_tk.s:.2f}s -> **{rate_topk:,.0f} configs/s**",
        f"best: {best.cost:.3f}s at "
        + ", ".join(f"{k}={v:g}" for k, v in best.assignment.items()),
        f"valid: {top.n_valid}/{top.n_evaluated}"
        + (f"; {sum(e.exact for e in top.entries)} top entries re-costed by "
           f"the exact simulator escape hatch" if any(e.exact for e in top.entries)
           else ""),
        "",
    ]

    # ---- 3: rates table (incl. the pure-Python oracle for context) ----
    n_py = min(2048 if not quick else 128, n_eq)
    sub = {k: v[:n_py] for k, v in cols.items()}
    with timer() as t_py:
        for i in range(n_py):
            job_model(
                hp.replace(
                    pSortMB=float(sub["pSortMB"][i]),
                    pSortFactor=int(sub["pSortFactor"][i]),
                    pNumReducers=int(sub["pNumReducers"][i]),
                ), st, cf,
            )
    py_rate = n_py / t_py.s

    rows = [
        ["python oracle (ref.job_model)", n_py, t_py.s, py_rate],
        ["unchunked jit(vmap) single-device", n_eq, t_un.s, n_eq / t_un.s],
        ["chunked+sharded full outputs", n_eq, t_ch.s, n_eq / t_ch.s],
        ["chunked+sharded streaming top-k", n_topk, t_tk.s, rate_topk],
    ]
    lines += table(["path", "configs", "wall s", "configs/s"], rows)
    lines += ["", f"speedup over python oracle: {rate_topk / py_rate:.0f}x"]
    write_md("whatif_throughput.md", "E5: what-if engine throughput", lines)
    return lines


if __name__ == "__main__":
    from .common import bench_main

    bench_main(run)
