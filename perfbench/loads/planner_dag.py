"""Traffic ``planner_dag``: one capacity-planning session for a DAG
workload, full ``search_topk`` calls over the configuration's cluster grid
on the seed's traces, run back to back (a closed loop), ranked by the
configuration's objective (the makespan).

The loop and the window are ``planner_grid``'s; the deployment is a Hive
query benchmark (:class:`perfbench.harness.hive_dag.HiveStreams`) and the
reference :mod:`perfbench.reference.wave_dag_ref`, which steps the DAG
release and the rack incast.

End-to-end metric: ``scenarios_per_s``, the grid rows of every chunk
finished inside the window over the window's whole time (each row rolls out
one scenario per trace).  In traced runs the program's counters and span
histograms over the window go into the run as ``"program"``."""

from __future__ import annotations

import math
import multiprocessing
from concurrent.futures import ProcessPoolExecutor

import numpy as np

from perfbench.harness.controls import bf16_evaluator
from perfbench.harness.hive_dag import HiveStreams
from perfbench.harness.program_trace import program_record
from perfbench.harness.topk import block_rows, merge_mismatch, same_entries
from perfbench.harness.window import Check
from perfbench.loads import planner_grid
from perfbench.reference import wave_dag_ref

__all__ = ["Load"]

#: worker processes of the reference comparison after the window, and the
#: most grid rows of one worker's task
WORKERS = 12
ROWS_PER_TASK = 8


class Load(planner_grid.Load):
    def __init__(self, cfg: dict, traffic: dict, seed: int, spans, plant=None,
                 control_dtype=None):
        if plant is bf16_evaluator:
            # the harness's control for a load it does not name: a planner's
            # knobs are whole numbers that bfloat16 holds exactly, so this
            # load's control is planner_grid's, the reference in bfloat16 in
            # the device's place
            import ml_dtypes

            plant, control_dtype = None, ml_dtypes.bfloat16
        self.fleet = HiveStreams(cfg, seed)
        self.traffic = traffic
        self.seed = seed
        self.spans = spans
        self.plant = plant
        self.k = int(traffic["k"])
        self.searches: list = []
        self.control_dtype = control_dtype
        self.registry = None

    def run(self, seconds: float) -> None:
        from repro.obs import current

        ob = current()
        self.registry = ob.registry if ob.enabled else None
        super().run(seconds)

    def layer_record(self) -> dict:
        rec = super().layer_record()
        if self.registry is not None:
            rec["program"] = program_record(self.registry)
        return rec

    def _references(self, jobs: list, dtypes) -> dict:
        """The reference in each of ``dtypes`` on each grid-row block of
        ``jobs``: per block, the cost of each row (mean over traces of the
        makespan), whether it converged, whether a task of it waited for a
        slot, and the float32 spacing at its last finish time.  Each precision, trace and run of at most
        ``ROWS_PER_TASK`` rows is a task of a worker process that imports
        numpy alone."""
        tasks, index = [], []
        for d in dtypes:
            for i, rows in enumerate(jobs):
                n = len(next(iter(rows.values())))
                for tr, sc in enumerate(self.fleet.scenarios(rows, self._ref_traces)):
                    for lo in range(0, n, ROWS_PER_TASK):
                        part = slice(lo, min(n, lo + ROWS_PER_TASK))
                        sub = {c: v if c == "dep" else v[part] for c, v in sc.items()}
                        tasks.append((sub, np.dtype(d).name))
                        index.append((np.dtype(d).name, i, tr))
        ctx = multiprocessing.get_context("spawn")
        with ProcessPoolExecutor(max_workers=min(len(tasks), WORKERS), mp_context=ctx) as ex:
            outs = list(ex.map(wave_dag_ref.simulate_makespan, tasks))
        per = len(self._ref_traces)
        res = {}
        for d in dtypes:
            res[d] = []
            for i in range(len(jobs)):
                part = [[np.concatenate(x) for x in zip(*(
                    o for o, key in zip(outs, index) if key == (np.dtype(d).name, i, tr)))]
                    for tr in range(per)]
                last = np.max([o[3] for o in part], axis=0)
                res[d].append((sum(o[0] for o in part) / per,
                               np.logical_and.reduce([o[1] for o in part]),
                               np.logical_or.reduce([o[2] for o in part]),
                               np.spacing(last.astype(np.float32)).astype(np.float64)))
        return res

    def check(self) -> list[Check]:
        """``planner_grid``'s checks, on every row of the winner's block;
        on ``check_blocks`` seeded others, their top-k and ``sample_rows``
        seeded rows of each (a fair row on a small cluster runs for about
        10^5 events, half a minute of the reference); and on the search's
        top-k rows outside them."""
        lim = self.traffic["limits"]
        if not self.searches:
            return [Check("complete_searches_missing", 1, lim["complete_searches_missing"])]
        self._ref_traces = self.fleet.reference_traces()
        grid, k = self.fleet.grid, self.k
        res, blocks = self.searches[-1]
        per_block = blocks[0][1]
        n_grid = math.prod(len(v) for v in grid.values())
        rng = np.random.default_rng([self.seed, 43])
        win = res.entries[0].index // per_block if res.entries else 0
        others = [i for i in range(len(blocks)) if i != win]
        picks = [win] + rng.choice(others, size=min(len(others), self.traffic["check_blocks"]),
                                   replace=False).tolist()
        jobs, chosen, whole = [], [], []        # whole: every row of its block compared
        for bi in picks:
            start, n, b = blocks[bi]
            keep = np.isfinite(b.costs)
            top = np.asarray(b.idx)[keep].astype(np.int64)
            pos = np.arange(n)
            if bi != win:
                left = np.setdiff1d(pos, top)
                sample = rng.choice(left, size=min(len(left), self.traffic["sample_rows"]),
                                    replace=False)
                pos = np.union1d(top, sample)
            jobs.append({c: v[pos] for c, v in block_rows(grid, start, n).items()})
            chosen.append((np.searchsorted(pos, top), np.asarray(b.costs, dtype=np.float64)[keep]))
            whole.append(bi == win)
        rest = [e for e in res.entries if not e.exact and e.index // per_block not in picks]
        if rest:
            jobs.append({c: np.asarray([e.assignment[c] for e in rest]) for c in grid})
            chosen.append((np.arange(len(rest)), np.asarray([e.cost for e in rest])))
        # the reference in float64 and in float32, the precision the device
        # path states; a device row agrees where it is close to either
        refs = self._references(jobs, (np.float64, np.float32))
        if self.control_dtype is not None:
            # the control: the reference in a lower precision in the device's place
            sub = [{c: v[pos] for c, v in rows.items()} for rows, (pos, _) in zip(jobs, chosen)]
            low = self._references(sub, (self.control_dtype,))[self.control_dtype]
            chosen = [(pos, np.where(ok, c, np.inf)) for (pos, _), (c, ok, *_) in zip(chosen, low)]
        err, mismatch, compared, contended, missed, gap = 0.0, 0, 0, 0, 0, 0
        for j, ((cost, conv, waited, ulp), (c32, conv32, *_), (pos, dev)) in enumerate(
                zip(refs[np.float64], refs[np.float32], chosen)):
            fin = np.isfinite(dev)
            mismatch += int(np.sum((conv[pos] != fin) & (conv32[pos] != fin)))
            near = np.full(len(pos), np.inf)
            for rc, ok in ((cost, conv), (c32, conv32)):
                both = ok[pos] & fin
                gap_ulp = np.abs(dev[both] - rc[pos][both]) / ulp[pos][both]
                near[both] = np.minimum(near[both], gap_ulp)
            if np.isfinite(near).any():
                err = max(err, float(near[np.isfinite(near)].max()))
            if j < len(picks):
                # rows of the block the selection missed, and its valid count
                left_out = np.ones(len(cost), dtype=bool)
                left_out[pos] = False
                kth = float(dev.max()) if len(pos) >= k else np.inf
                margin = self.traffic["missed_margin_ulp"] * ulp
                cheaper = conv & conv32 & (cost + margin < kth) & (c32 + margin < kth)
                missed += int(np.sum(left_out & cheaper))
                if whole[j]:
                    n_dev = int(blocks[picks[j]][2].n_valid)
                    gap += max(0, int((conv & conv32).sum()) - n_dev,
                               n_dev - int((conv | conv32).sum()))
                compared += int(conv.sum())
                contended += int((conv & waited).sum())
            else:
                compared += int((conv[pos] & fin).sum())
                contended += int((conv[pos] & fin & waited[pos]).sum())
        self.notes = [f"{len(self.searches)} complete searches; {compared} rows compared with "
                      f"the reference, {contended} of them with tasks waiting for slots; "
                      f"blocks checked {picks}"]
        return [
            Check("complete_searches_missing", 0, lim["complete_searches_missing"]),
            Check("searches_differing", sum(not same_entries(r, res) for r, _ in self.searches),
                  lim["searches_differing"]),
            Check("rows_missing", abs(n_grid - sum(n for _, n, _ in blocks)),
                  lim["rows_missing"]),
            Check("merge_mismatch", merge_mismatch(grid, blocks, res.entries, k),
                  lim["merge_mismatch"]),
            Check("uncontended_share", 1.0 - contended / max(compared, 1),
                  lim["uncontended_share"]),
            Check("makespan_gap_ulp", err, lim["makespan_gap_ulp"]),
            Check("valid_mismatch", mismatch, lim["valid_mismatch"]),
            Check("missed_rows", missed, lim["missed_rows"]),
            Check("valid_count_gap", gap, lim["valid_count_gap"]),
        ]
