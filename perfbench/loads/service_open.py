"""Traffic ``service_open``: independent users asking what-if questions of
one job through the query service (``repro.api.serve``), as an open loop at
a fixed rate.

The traffic file gives the rate and the query kinds: each kind has its own
key-set (so its own executable), its entry (``probe``, ``sweep``, ``grid``,
``phase_query``), its share of the queries and, for grids, the range of rows.
Every seed sends the same number of queries of each kind, of the same sizes,
with the same set of gaps between them, in a seeded order and with seeded
values from the configuration's grid.

End-to-end metrics: ``query_p50_ms`` and ``query_p95_ms`` over every query due
in the window, each timed from when it was due to be sent until its future
resolved; a query that never resolves counts as infinitely late."""

from __future__ import annotations

import math
import time

import numpy as np

from perfbench.harness.jobs import Job, OracleRows
from perfbench.harness.stats import apportion, percentile
from perfbench.harness.topk import block_rows
from perfbench.harness.window import Check, TimedEvaluator

__all__ = ["Load", "sub_grid_sizes", "make_queries"]


def sub_grid_sizes(lengths: list[int], target: int) -> list[int]:
    """Values per key of a sub-grid of about ``target`` rows: keys grow in
    turn while the product stays at or under the target (at least one each)."""
    sizes = [1] * len(lengths)
    grew = True
    while grew:
        grew = False
        for i, n in enumerate(lengths):
            prod = math.prod(sizes)
            if sizes[i] < n and prod // sizes[i] * (sizes[i] + 1) <= target:
                sizes[i] += 1
                grew = True
    return sizes


def make_queries(traffic: dict, grid: dict, n: int, seconds: float, seed: int) -> list:
    """``n`` queries ``(due_s, kind, entry, payload)`` sorted by due time."""
    rng = np.random.default_rng([seed, 29])
    kinds = traffic["kinds"]
    out = []
    for kind, m in zip(kinds, apportion([k["share"] for k in kinds], n)):
        keys = kind["keys"]
        lo, hi = kind.get("rows", (1, 1))
        targets = np.geomspace(lo, hi, m) if m > 1 else np.asarray([lo] * m, float)
        for i, target in enumerate(rng.permutation(targets)):
            pick = {k: float(rng.choice(grid[k])) for k in keys}
            if kind["entry"] == "probe":
                payload = pick
            elif kind["entry"] == "sweep":
                key = keys[i % len(keys)]
                payload = (key, list(grid[key]), {k: v for k, v in pick.items() if k != key})
            else:
                sizes = sub_grid_sizes([len(grid[k]) for k in keys], int(round(target)))
                space = {k: sorted(rng.choice(grid[k], size=s, replace=False).tolist())
                         for k, s in zip(keys, sizes)}
                if kind["entry"] == "phase_query":
                    payload = (space, str(rng.choice(traffic["phases"])))
                else:
                    payload = space
            out.append([kind["name"], kind["entry"], payload])
    order = rng.permutation(len(out))
    q = np.arange(len(out)) + 0.5
    gaps = rng.permutation(-np.log1p(-q / len(out)))     # exponential quantiles
    due = np.cumsum(gaps) - gaps[0]
    due *= seconds / (due[-1] + gaps[0]) if len(due) else 1.0
    return [(float(t), *out[i]) for t, i in zip(due, order)]


def query_rows(entry: str, payload) -> dict:
    """The query's rows as columns (scalars broadcast)."""
    if entry == "probe":
        return {k: np.asarray([v]) for k, v in payload.items()}
    if entry == "sweep":
        key, values, base = payload
        cols = {k: np.full(len(values), v) for k, v in base.items()}
        cols[key] = np.asarray(values, dtype=np.float64)
        return cols
    space = payload[0] if entry == "phase_query" else payload
    return block_rows(space, 0, math.prod(len(v) for v in space.values()))


class Load:
    def __init__(self, cfg: dict, traffic: dict, seed: int, spans, plant=None):
        self.job = Job(cfg, seed)
        self.traffic = traffic
        self.seed = seed
        self.spans = spans
        self.plant = plant
        self.window_notes: list[str] = []

    def _submit(self, entry: str, payload):
        svc = self.svc
        if entry == "probe":
            return svc.probe(payload, exact_fallback=False)
        if entry == "sweep":
            key, values, base = payload
            return svc.sweep(key, values, base=base, exact_fallback=False)
        if entry == "grid":
            return svc.grid(payload, exact_fallback=False)
        space, phase = payload
        return svc.phase_query(query_rows(entry, payload), phase=phase)

    def setup(self) -> None:
        import repro.api as api

        inner = api.get_evaluator(self.job.program_spec())   # the program's defaults
        self.ev = TimedEvaluator(self.plant(inner) if self.plant else inner, self.spans)
        self.svc = api.serve(self.ev)
        self.chunk = inner.chunk
        # one query of each kind compiles its key-set's executable
        warm = make_queries(self.traffic, self.job.grid, len(self.traffic["kinds"]),
                            1.0, self.seed + 1)
        by_kind = {}
        for _, name, entry, payload in warm:
            by_kind.setdefault(name, (entry, payload))
        for kind in self.traffic["kinds"]:
            if kind["name"] not in by_kind:
                wq = make_queries({**self.traffic, "kinds": [kind]}, self.job.grid, 1,
                                  1.0, self.seed + 1)
                by_kind[kind["name"]] = tuple(wq[0][2:])
        for f in [self._submit(*q) for q in by_kind.values()]:
            f.result()

    def run(self, seconds: float) -> None:
        n = max(1, round(float(self.traffic["rate_qps"]) * seconds))
        self.queries = make_queries(self.traffic, self.job.grid, n, seconds, self.seed)
        self.futures, self.sent, self.done = [], [], [None] * n
        self.before = self.svc.summary()
        t0 = self.t0 = time.perf_counter()
        for i, (due, _, entry, payload) in enumerate(self.queries):
            wait = t0 + due - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            self.sent.append(time.perf_counter() - t0 - due)
            with self.spans.span("submit"):
                f = self._submit(entry, payload)
            f.add_done_callback(lambda _f, i=i: self.done.__setitem__(i, time.perf_counter()))
            self.futures.append(f)
        rest = t0 + seconds - time.perf_counter()
        if rest > 0:
            time.sleep(rest)
        self.backlog = sum(1 for d in self.done if d is None)

    def drain(self) -> None:
        deadline = time.perf_counter() + float(self.traffic["wait_s"])
        self.results = []
        for f in self.futures:
            try:
                self.results.append(f.result(timeout=max(0.0, deadline - time.perf_counter())))
            except Exception as e:          # noqa: BLE001 - a failed query is counted
                self.results.append(e)
        after = self.svc.summary()
        self.service = {k: after[k] - self.before[k] for k in ("rows", "chunks", "queries")}
        lat = sorted((d - self.t0 - q[0]) if d is not None else math.inf
                     for d, q in zip(self.done, self.queries))
        self.latency = lat
        late = sorted(self.sent)
        self.window_notes = [
            f"{len(self.queries)} queries due in the window; backlog at its close "
            f"{self.backlog}; generator lateness p50 {percentile(late, 50)!r} s, "
            f"max {late[-1]!r} s",
            f"service in the window: {self.service}",
        ]

    def end_to_end(self) -> dict:
        return {"query_p50_ms": 1e3 * percentile(self.latency, 50),
                "query_p95_ms": 1e3 * percentile(self.latency, 95)}

    def counts(self) -> tuple[int, int]:
        failed = sum(1 for r in self.results if isinstance(r, Exception))
        return len(self.queries), failed

    def layer_record(self) -> dict:
        return {"service": self.service, **self.end_to_end()}

    def release(self) -> None:
        self.svc.close()
        self.svc = self.ev = None

    def check(self) -> list[Check]:
        lim = self.traffic["limits"]
        rng = np.random.default_rng([self.seed, 31])
        answered = [i for i, r in enumerate(self.results) if not isinstance(r, Exception)]
        size = {i: len(next(iter(query_rows(*self.queries[i][2:]).values())))
                for i in answered}
        picks = set(rng.choice(answered, size=min(len(answered),
                                                  self.traffic["check_queries"]),
                               replace=False).tolist()) if answered else set()
        if answered:
            picks.add(max(answered, key=size.get))
        orows = OracleRows(self.job)
        short = 0
        for i in sorted(picks):
            _, _, entry, payload = self.queries[i]
            rows = query_rows(entry, payload)
            r = self.results[i]
            if entry == "phase_query":
                cost, valid = np.asarray(r.report.total_cost), np.asarray(r.report.valid) > 0
            else:
                cost, valid = r.outputs["j_totalCost"], r.outputs["valid"] > 0
            if len(cost) != size[i]:
                short += 1
                continue
            take = rng.choice(size[i], size=min(size[i], self.traffic["check_rows"]),
                              replace=False)
            for j in np.sort(take):
                orows.add({k: float(v[j]) for k, v in rows.items()}, cost[j], valid[j])
        self.notes = [f"{len(picks)} queries checked, {orows.rows} rows compared with the "
                      f"oracle, {orows.flipped} matched with edge decisions flipped"
                      ] + orows.edge_rows + orows.worst[:10]
        return [
            Check("queries_unanswered", len(self.queries) - len(answered),
                  lim["queries_unanswered"]),
            Check("answers_short", short, lim["answers_short"]),
            Check("rel_err", orows.rel_err, lim["rel_err"]),
            Check("valid_mismatch", orows.valid_mismatch, lim["valid_mismatch"]),
        ]
