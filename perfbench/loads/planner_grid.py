"""Traffic ``planner_grid``: one capacity-planning session, full
``search_topk`` calls over the configuration's cluster grid on the seed's
traces, run back to back (a closed loop).

End-to-end metric: ``scenarios_per_s``, the grid rows of every chunk finished
inside the window over the window's whole time (each row rolls out one
scenario per trace)."""

from __future__ import annotations

import math
import multiprocessing
import time
from concurrent.futures import ProcessPoolExecutor

import numpy as np

from perfbench.harness.cluster import Fleet
from perfbench.harness.topk import block_rows, merge_mismatch, same_entries
from perfbench.harness.window import Check, TimedEvaluator
from perfbench.reference import wave_ref

__all__ = ["Load"]

#: worker processes of the reference comparison after the window
WORKERS = 8


class Load:
    def __init__(self, cfg: dict, traffic: dict, seed: int, spans, plant=None,
                 control_dtype=None):
        self.fleet = Fleet(cfg, seed)
        self.traffic = traffic
        self.seed = seed
        self.spans = spans
        self.plant = plant
        self.k = int(traffic["k"])
        self.searches: list = []
        self.control_dtype = control_dtype

    def _search(self, space):
        from repro.search import search_topk

        self.ev.begin_search()
        with self.spans.span("search"):
            return search_topk(self.ev, space, k=self.k,
                               exact_fallback=bool(self.traffic["exact_fallback"]))

    def setup(self) -> None:
        inner = self.fleet.program_evaluator()
        self.ev = TimedEvaluator(self.plant(inner) if self.plant else inner, self.spans)
        self.num_devices = inner.num_devices
        # the step cap of each chunk is a compile key: one search warms them all
        self._search(self.fleet.grid)

    def run(self, seconds: float) -> None:
        self.seconds = seconds
        self.ev.rows_done = self.ev.blocks_done = 0
        self.ev.deadline = time.perf_counter() + seconds
        # the search running at the deadline runs to its end (its later
        # blocks do not count), so every window leaves a whole search to check
        while time.perf_counter() < self.ev.deadline:
            res = self._search(self.fleet.grid)
            self.searches.append((res, list(self.ev.blocks)))

    def end_to_end(self) -> dict:
        return {"scenarios_per_s": self.ev.rows_done / self.seconds}

    def counts(self) -> tuple[int, int]:
        return self.ev.blocks_done, 0

    def layer_record(self) -> dict:
        return {"num_devices": self.num_devices}

    def release(self) -> None:
        self.ev = None

    def _references(self, jobs: list, dtype=np.float64) -> list[tuple]:
        """The reference on each ``(rows, caps)`` of ``jobs``: the cost of
        each grid row (mean over traces of the p95 latency), whether it
        converged within its block's cap of events, whether a task of it
        waited for a slot, and the float32 spacing at its last finish time.
        Every (rows, trace) pair runs in a worker process of its own that
        imports numpy alone."""
        tasks = [(sc, caps, np.dtype(dtype).name) for rows, caps in jobs
                 for sc in self.fleet.scenarios(rows, self._ref_traces)]
        ctx = multiprocessing.get_context("spawn")
        with ProcessPoolExecutor(max_workers=min(len(tasks), WORKERS), mp_context=ctx) as ex:
            outs = list(ex.map(wave_ref.simulate_p95, tasks))
        per = len(self._ref_traces)
        res = []
        for i in range(len(jobs)):
            part = outs[i * per:(i + 1) * per]
            last = np.max([o[3] for o in part], axis=0)
            res.append((sum(o[0] for o in part) / per,
                        np.logical_and.reduce([o[1] for o in part]),
                        np.logical_or.reduce([o[2] for o in part]),
                        np.spacing(last.astype(np.float32)).astype(np.float64)))
        return res

    def _cap(self, start: int, n: int) -> int:
        """The planner's event cap for the chunk holding rows ``[start,
        start + n)``: every trace's jobs on every row's slots."""
        cols = block_rows(self.fleet.grid, start, n)
        nodes = np.round(cols["pNumNodes"])
        tasks = [[(k[0].pNumMappers, k[0].pNumReducers) for _, k in tr]
                 for tr in self._ref_traces]
        n_maps = np.concatenate([np.tile([m for m, _ in t], (n, 1)) for t in tasks])
        n_reds = np.concatenate([np.tile([r for _, r in t], (n, 1)) for t in tasks])
        ms = np.tile(nodes * np.round(cols["pMaxMapsPerNode"]), len(tasks))
        rs = np.tile(nodes * np.round(cols["pMaxRedPerNode"]), len(tasks))
        return wave_ref.step_cap(n_maps, n_reds, ms, rs)

    def check(self) -> list[Check]:
        lim = self.traffic["limits"]
        if not self.searches:
            return [Check("complete_searches_missing", 1, lim["complete_searches_missing"])]
        self._ref_traces = self.fleet.reference_traces()
        grid, k = self.fleet.grid, self.k
        res, blocks = self.searches[-1]
        per_block = blocks[0][1]
        n_grid = math.prod(len(v) for v in grid.values())
        # every row of the winner's block and of seeded others, and the
        # search's top-k rows outside them
        rng = np.random.default_rng([self.seed, 43])
        win = [res.entries[0].index // per_block] if res.entries else []
        others = [i for i in range(len(blocks)) if i not in win]
        picks = sorted(win + rng.choice(others, size=min(len(others), self.traffic["check_blocks"]),
                                        replace=False).tolist())
        rest = [e for e in res.entries if not e.exact and e.index // per_block not in picks]
        jobs = [(block_rows(grid, *blocks[bi][:2]), self._cap(*blocks[bi][:2])) for bi in picks]
        chosen = []                               # (row positions, device costs) per job
        for bi in picks:
            b = blocks[bi][2]
            keep = np.isfinite(b.costs)
            chosen.append((np.asarray(b.idx)[keep].astype(np.int64),
                           np.asarray(b.costs, dtype=np.float64)[keep]))
        if rest:
            jobs.append(({c: np.asarray([e.assignment[c] for e in rest]) for c in grid},
                         np.asarray([self._cap(*blocks[e.index // per_block][:2]) for e in rest])))
            chosen.append((np.arange(len(rest)), np.asarray([e.cost for e in rest])))
        # the reference in float64 and in float32, the precision the device
        # path states: event times accumulate rounding over hundreds of waves,
        # so a float32 schedule may drift from the float64 one by hundreds of
        # float32 steps at the last finish (the float32 reference drifts the
        # same way); a device row agrees where it is close to either
        refs = self._references(jobs)
        refs32 = self._references(jobs, np.float32)
        if self.control_dtype is not None:
            # the control: the reference in a lower precision in the device's place
            sub = [({c: v[pos] for c, v in rows.items()}, caps if np.ndim(caps) == 0 else caps[pos])
                   for (rows, caps), (pos, _) in zip(jobs, chosen)]
            low = self._references(sub, self.control_dtype)
            chosen = [(pos, np.where(ok, c, np.inf)) for (pos, _), (c, ok, *_) in zip(chosen, low)]
        err, mismatch, compared, contended, missed, gap = 0.0, 0, 0, 0, 0, 0
        for j, ((cost, conv, waited, ulp), (c32, conv32, *_), (pos, dev)) in enumerate(
                zip(refs, refs32, chosen)):
            fin = np.isfinite(dev)
            mismatch += int(np.sum((conv[pos] != fin) & (conv32[pos] != fin)))
            near = np.full(len(pos), np.inf)
            for rc, ok in ((cost, conv), (c32, conv32)):
                both = ok[pos] & fin
                near[both] = np.minimum(near[both], np.abs(dev[both] - rc[pos][both]) / ulp[pos][both])
            if np.isfinite(near).any():
                err = max(err, float(near[np.isfinite(near)].max()))
            if j < len(picks):
                # the whole block: rows the selection missed, and its valid count
                b = blocks[picks[j]][2]
                left_out = np.ones(len(cost), dtype=bool)
                left_out[pos] = False
                kth = float(dev.max()) if len(pos) >= k else np.inf
                margin = self.traffic["missed_margin_ulp"] * ulp
                cheaper = conv & conv32 & (cost + margin < kth) & (c32 + margin < kth)
                missed += int(np.sum(left_out & cheaper))
                n_dev = int(b.n_valid)
                gap += max(0, int((conv & conv32).sum()) - n_dev, n_dev - int((conv | conv32).sum()))
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
            Check("p95_gap_ulp", err, lim["p95_gap_ulp"]),
            Check("valid_mismatch", mismatch, lim["valid_mismatch"]),
            Check("missed_rows", missed, lim["missed_rows"]),
            Check("valid_count_gap", gap, lim["valid_count_gap"]),
        ]
