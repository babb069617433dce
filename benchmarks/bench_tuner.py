"""E6 — configuration tuning: does the search find the true optimum, and
how many model evaluations does each strategy need?

Ground truth = exhaustive grid, streamed through the chunked/sharded
evaluator with on-device top-k (:mod:`repro.search`); the regret column is
(found - optimum)/optimum, the configs/s column is the evaluator's
streaming throughput for that strategy.

The gradient row relaxes the space continuously and differentiates the
job model itself (:func:`repro.search.gradient_descent_ev`), so its
``evals`` column counts only the final candidate-validation batch — the
descent steps never touch the evaluator.  Because continuous values
between grid candidates are admissible, its regret can be *negative*.

``--smoke`` is the CI gate: gradient descent must land within 5% of the
exhaustive grid optimum using fewer evaluator calls than coordinate
descent.
"""

from __future__ import annotations

import jax

from repro.core.hadoop.params import CostFactors, HadoopParams, MiB, ProfileStats
from repro.search import (
    ChunkedEvaluator,
    coordinate_descent_ev,
    gradient_descent_ev,
    grid_search_ev,
    random_search_ev,
)
from .common import table, timer, write_md

SPACE = {
    "pSortMB": [16, 32, 64, 100, 128, 256, 512],
    "pSortFactor": [3, 5, 10, 20, 50, 100],
    "pNumReducers": [2, 4, 8, 16, 32, 64, 128],
    "pShuffleInBufPerc": [0.3, 0.5, 0.7, 0.9],
    "pUseCombine": [0.0, 1.0],
}

#: CI gate — gradient descent must land within this relative regret of the
#: exhaustive optimum (it typically *beats* the grid via off-grid values).
_SMOKE_REGRET_MAX = 0.05


def run(quick: bool = False, smoke: bool = False) -> list[str]:
    # The closed-form model sums per-phase costs that differ by ~9 orders of
    # magnitude; the descent strategies need float64 to keep gradients and
    # regret comparisons meaningful.  Scoped to this run: the process's
    # precision is left as it was found.
    with jax.enable_x64(True):
        return _run(smoke)


def _run(smoke: bool) -> list[str]:
    hp = HadoopParams(pNumNodes=16, pNumMappers=128, pUseCombine=True,
                      pSplitSize=256 * MiB)
    st = ProfileStats(sMapSizeSel=1.2, sMapPairsSel=2.0,
                      sCombineSizeSel=0.35, sCombinePairsSel=0.35)
    cf = CostFactors()
    ev = ChunkedEvaluator(hp, st, cf, chunk=1 << 12)

    with timer() as t_ex:
        exact = grid_search_ev(ev, SPACE)
    rows = [["exhaustive (streamed top-k)", exact.evaluations, exact.best_cost,
             0.0, t_ex.s, exact.evaluations / t_ex.s]]
    results: dict[str, object] = {}
    for name, fn in [
        ("coordinate descent", lambda: coordinate_descent_ev(ev, SPACE)),
        ("gradient descent", lambda: gradient_descent_ev(ev, SPACE)),
        ("random-512", lambda: random_search_ev(ev, SPACE, samples=512)),
        ("random-64", lambda: random_search_ev(ev, SPACE, samples=64)),
    ]:
        with timer() as t:
            res = fn()
        results[name] = res
        regret = (res.best_cost - exact.best_cost) / exact.best_cost
        rows.append([name, res.evaluations, res.best_cost, regret, t.s,
                     res.evaluations / t.s])

    lines = [f"space size = {exact.evaluations} configs; "
             f"optimum {exact.best_cost:.3f}s at {exact.best_assignment} "
             f"(devices={ev.num_devices}, chunk={ev.chunk})", ""]
    lines += table(
        ["strategy", "evals", "best cost s", "regret", "wall s", "configs/s"],
        rows,
    )

    grad = results["gradient descent"]
    coord = results["coordinate descent"]
    grad_regret = (grad.best_cost - exact.best_cost) / exact.best_cost
    lines += ["", f"gradient regret vs exhaustive = {grad_regret:+.4f} "
                  f"(gate <= {_SMOKE_REGRET_MAX:+.2f}); evaluator calls "
                  f"{grad.evaluations} vs coordinate's {coord.evaluations}"]
    if smoke:
        assert grad_regret <= _SMOKE_REGRET_MAX, (
            f"gradient descent regret {grad_regret:.4f} exceeds "
            f"{_SMOKE_REGRET_MAX} vs the exhaustive optimum"
        )
        assert grad.evaluations < coord.evaluations, (
            f"gradient descent used {grad.evaluations} evaluator calls, "
            f"not fewer than coordinate descent's {coord.evaluations}"
        )
        lines += ["", "smoke assertions passed: gradient within regret gate "
                      "in fewer evaluator calls than coordinate descent"]

    write_md("tuner.md", "E6: configuration tuner", lines)
    return lines


if __name__ == "__main__":
    from .common import bench_main

    bench_main(run)
