"""Traffic ``grid_topk``: one tuning session, full ``search_topk`` calls
over the configuration's grid run back to back (a closed loop).

End-to-end metric: ``configs_per_s``, the rows of every chunk finished inside
the window over the window's whole time."""

from __future__ import annotations

import multiprocessing
import time
from concurrent.futures import ProcessPoolExecutor

import numpy as np

from perfbench.harness.jobs import Job, OracleRows, row_flags
from perfbench.harness.topk import block_rows, merge_mismatch, same_entries
from perfbench.harness.window import Check, TimedEvaluator

__all__ = ["Load"]

#: worker processes of the oracle comparison after the window, and rows per task
WORKERS = 8
ROWS_PER_TASK = 512


class Load:
    """``plant`` (tests and control readings only) wraps the program's
    evaluator before the harness does."""

    def __init__(self, cfg: dict, traffic: dict, seed: int, spans, plant=None):
        self.job = Job(cfg, seed)
        self.traffic = traffic
        self.seed = seed
        self.spans = spans
        self.plant = plant
        self.k = int(traffic["k"])
        self.searches: list = []
        self.seconds = 0.0

    def _search(self, space):
        from repro.search import search_topk

        self.ev.begin_search()
        with self.spans.span("search"):
            return search_topk(self.ev, space, k=self.k,
                               exact_fallback=bool(self.traffic["exact_fallback"]))

    def setup(self) -> None:
        from repro.api import get_evaluator

        inner = get_evaluator(self.job.program_spec())   # the program's defaults
        self.ev = TimedEvaluator(self.plant(inner) if self.plant else inner, self.spans)
        self.num_devices = inner.num_devices
        self.chunk = inner.chunk
        # the grid's key-set compiles one top-k program; each block length the
        # search streams (a full chunk and the tail) also compiles the casts
        # of its columns
        n = int(np.prod([len(v) for v in self.job.grid.values()]))
        for rows in {min(n, self.chunk), n % self.chunk or self.chunk}:
            self.ev.chunk_topk(block_rows(self.job.grid, 0, rows), self.k)
        self._search({k: v[:2] for k, v in self.job.grid.items()})

    def run(self, seconds: float) -> None:
        self.seconds = seconds
        self.ev.rows_done = self.ev.blocks_done = 0
        self.ev.deadline = time.perf_counter() + seconds
        # the search running at the deadline runs to its end (its later
        # blocks do not count), so every window leaves a whole search to check
        while time.perf_counter() < self.ev.deadline:
            res = self._search(self.job.grid)
            self.searches.append((res, list(self.ev.blocks)))

    def end_to_end(self) -> dict:
        return {"configs_per_s": self.ev.rows_done / self.seconds}

    def counts(self) -> tuple[int, int]:
        return self.ev.blocks_done, 0

    def layer_record(self) -> dict:
        return {"rows_per_chunk": self.chunk, "num_devices": self.num_devices,
                "swept_keys": len(self.job.grid)}

    def release(self) -> None:
        self.ev = None

    def check(self) -> list[Check]:
        lim = self.traffic["limits"]
        if not self.searches:
            return [Check("complete_searches_missing", 1, lim["complete_searches_missing"])]
        grid, k = self.job.grid, self.k
        res, blocks = self.searches[-1]
        n_grid = int(np.prod([len(v) for v in grid.values()]))
        checks = [
            Check("complete_searches_missing", 0, lim["complete_searches_missing"]),
            Check("searches_differing",
                  sum(not same_entries(r, res) for r, _ in self.searches),
                  lim["searches_differing"]),
            Check("rows_missing", abs(n_grid - sum(n for _, n, _ in blocks)),
                  lim["rows_missing"]),
            Check("merge_mismatch", merge_mismatch(grid, blocks, res.entries, k),
                  lim["merge_mismatch"]),
        ]
        orows = OracleRows(self.job)
        for e in res.entries:
            if not e.exact:
                orows.add(e.assignment, e.cost, e.valid)
        rng = np.random.default_rng([self.seed, 17])
        picks = set(rng.choice(len(blocks), size=min(len(blocks), self.traffic["check_blocks"]),
                               replace=False).tolist())
        if res.entries:
            picks.add(res.entries[0].index // blocks[0][1])
        self.block_notes = []
        missed, gap = self._check_blocks(orows, [blocks[bi] for bi in sorted(picks)])
        checks += [
            Check("rel_err", orows.rel_err, lim["rel_err"]),
            Check("valid_mismatch", orows.valid_mismatch, lim["valid_mismatch"]),
            Check("missed_rows", missed, lim["missed_rows"]),
            Check("valid_count_gap", gap, lim["valid_count_gap"]),
        ]
        self.notes = [f"{len(self.searches)} complete searches; {orows.rows} rows "
                      f"compared with the oracle, {orows.flipped} of them matched "
                      f"with edge decisions flipped; blocks checked {sorted(picks)}"
                      ] + self.block_notes + orows.edge_rows + orows.worst[:10]
        return checks

    def _check_blocks(self, orows: OracleRows, blocks: list) -> tuple[int, int]:
        """Compare each block's device top-k with the oracle over every row of
        the block: (rows the selection missed, valid-count gap), summed over
        the blocks.  A row the device left out is missed where the oracle
        calls it valid and cheaper than the device's k-th winner under every
        way its edge decisions can go.  The device's valid count has to lie
        between the rows valid under every way their edge decisions can go
        and those valid under some way: one flipped floor can move a round
        count by F - 1 (a shuffle merge more or less), so an edge row's flag
        may change far from the F**2 bound.  The gap is how far the count lies
        outside that range.  The oracle runs in worker processes that import
        numpy and the reference alone."""
        tasks, chosen = [], []
        for start, n, b in blocks:
            keep = np.isfinite(b.costs)
            idx, costs = np.asarray(b.idx)[keep], np.asarray(b.costs)[keep]
            cols = block_rows(self.job.grid, start, n)
            for i, c in zip(idx, costs):
                orows.add({k: float(v[int(i)]) for k, v in cols.items()}, c, True)
            chosen.append(idx)
            thr = (float(costs.max()) * (1.0 - self.traffic["limits"]["rel_err"])
                   if len(idx) >= self.k else np.inf)
            for lo in range(0, n, ROWS_PER_TASK):
                part = {k: v[lo:lo + ROWS_PER_TASK] for k, v in cols.items()}
                tasks.append((self.job.cfg, self.seed, part, thr))
        ctx = multiprocessing.get_context("spawn")
        with ProcessPoolExecutor(max_workers=min(WORKERS, len(tasks)), mp_context=ctx) as ex:
            flags = np.concatenate(list(ex.map(row_flags, tasks)))
        missed = gap = 0
        at = 0
        for (start, n, b), idx in zip(blocks, chosen):
            own, lo, hi, cheaper = flags[at:at + n].T
            at += n
            left_out = np.ones(n, dtype=bool)
            left_out[idx.astype(np.int64)] = False
            missed += int(np.sum(left_out & cheaper))
            n_dev = int(b.n_valid)
            gap += max(0, int(lo.sum()) - n_dev, n_dev - int(hi.sum()))
            self.block_notes.append(f"block at {start}: {n_dev} rows valid on the device, "
                                    f"{int(own.sum())} by the oracle's own way, {int(lo.sum())} "
                                    f"to {int(hi.sum())} as its edge decisions go")
        return missed, gap
