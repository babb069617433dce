"""Differentiable loop: observe -> calibrate by autodiff -> tune by gradient.

The closed-form job model is branch-free JAX end-to-end (straight-through
round counts, double-``where`` guarded divisions), so the same graph that
*predicts* a cost can be differentiated — against its Table-3 cost factors
(calibration) or against the configuration knobs (search):

1. OBSERVE    run a few jobs on the MapReduce-on-JAX engine and keep only
              ``(JobSpec, wall seconds)`` pairs — no phase timings needed,
              unlike the least-squares profiler fit.
2. CALIBRATE  ``api.calibrate`` fits the cost factors by ``jax.grad`` on
              the relative-error loss (repro.optim AdamW, per-axis
              log/logit transforms keep every step in-domain).
3. TUNE       ``api.tune(strategy="gradient")`` relaxes the search space
              continuously and descends on the model itself; candidates
              are rounded, validated against the declared predicates, and
              re-costed through the evaluator before being reported.

Run:  PYTHONPATH=src python examples/calibrate_and_tune.py
"""

import jax

import repro.api as api
from repro.calib import Observation
from repro.core.hadoop.params import HadoopParams, MiB
from repro.mapreduce import JOBS
from repro.mapreduce.profiler import fit_cost_factors, predict, run_measured
from repro.spec import JobSpec


def main() -> None:
    # Cost factors span ~1e-9..1e-7 s/byte, so this loop runs in float64,
    # scoped to main(): importing the module changes no precision.
    with jax.enable_x64(True):
        job = JOBS["wordcount"]
        N = 120_000
        base_hp = HadoopParams(
            pNumMappers=4, pNumReducers=2, pUseCombine=True,
            pSortMB=0.25, pSortFactor=3,                      # deliberately poor
            pSplitSize=N / 4 * job.pair_width, pTaskMem=8 * MiB,
        )

        # ---- 1: observe three configurations on the live engine ----
        # Probes must sit inside the closed-form merge domain (the model weighs
        # valid==0 rows out of the fit, and calibrate() refuses an all-invalid
        # set) — so unlike the tuning start point, none uses pSortMB=0.25.
        probes = [
            base_hp.replace(pSortMB=1.0, pSortFactor=8),
            base_hp.replace(pSortMB=2.0, pSortFactor=10),
            base_hp.replace(pSortMB=1.0, pSortFactor=8, pNumReducers=8),
        ]
        runs = [run_measured(job, hp, N, seed=1) for hp in probes]
        stats = runs[0].stats

        # the lstsq profiler fit needs the per-phase timing breakdown of each run;
        # the autodiff fit needs only what a production log would have: the spec
        # that ran and how long it took.  Seed it from the lstsq fit's factors so
        # the comparison is "does gradient refinement improve the same start".
        seed_costs = fit_cost_factors(runs)
        observations = [
            Observation(
                spec=JobSpec(params=r.hp, stats=r.stats, costs=seed_costs),
                cost=r.wall_s,
            )
            for r in runs
        ]

        # ---- 2: calibrate the cost factors by jax.grad ----
        report = api.calibrate(observations, steps=300)
        print("== calibration (autodiff on the model itself) ==")
        print(report.summary())
        fitted_costs = seed_costs.replace(**report.fitted)

        print("\nper-run relative error, lstsq -> autodiff:")
        for r in runs:
            e0 = abs(predict(r.hp, stats, seed_costs) - r.wall_s) / r.wall_s
            e1 = abs(predict(r.hp, stats, fitted_costs) - r.wall_s) / r.wall_s
            print(f"  {r.hp.pSortMB:6.2f}MB sort, {r.hp.pNumReducers:2d} reducers: "
                  f"{e0:6.1%} -> {e1:6.1%}")

        # ---- 3: tune the knobs by gradient descent on the calibrated model ----
        spec = JobSpec(params=base_hp, stats=stats, costs=fitted_costs)
        space = {
            "pSortMB": [0.25, 0.5, 1.0, 2.0, 4.0],
            "pSortFactor": [3, 5, 8, 16],
            "pNumReducers": [2, 4, 8],
            "pUseCombine": [0.0, 1.0],
        }
        grad = api.tune(spec, space, strategy="gradient")
        coord = api.tune(spec, space, strategy="descent")
        print("\n== tuning on the calibrated model ==")
        print(f"coordinate descent: {coord.best_cost:8.3f}s "
              f"in {coord.evaluations} evaluator calls")
        print(f"gradient descent  : {grad.best_cost:8.3f}s "
              f"in {grad.evaluations} evaluator calls")
        print(f"recommended config: {grad.best_assignment}")


if __name__ == "__main__":
    main()
