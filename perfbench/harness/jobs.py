"""A Hadoop job deployment (a ``hadoop_job`` configuration file) as the
program takes it and as the float64 oracle takes it, and the comparison of
device rows with the oracle."""

from __future__ import annotations

import dataclasses
import functools
import itertools
import math

import numpy as np

from perfbench.reference import hadoop_params as ref_params
from perfbench.reference.hadoop_ref import edges

__all__ = ["ORACLE_RTOL", "MAX_FLIPPED_EDGES", "ROUND_COUNTS", "Job", "OracleRows", "row_flags"]

#: Relative tolerance of device costs against the float64 oracle.  The device
#: path runs in float32 (unit roundoff 6e-8); Eq. 98 is a sum of non-negative
#: per-phase products and the longest chain of dependent roundings through
#: Eqs. 2-97 is a few dozen operations, so a row whose round counts match the
#: oracle's stays within about 50 * 6e-8 = 3e-6, and the TPU's divide and
#: log2 add a few ulp each.  1e-4 leaves 30x over that bound, while a wrong
#: equation, or the same equations in bfloat16, is off by far more.
ORACLE_RTOL = 1e-4
#: A row with more edge decisions than this is compared with its first ones
#: flipped only (2**8 oracle runs at most per row).
MAX_FLIPPED_EDGES = 8
#: Round counts of the job model that an edge row is named by: the ones that
#: differ between the oracle's own way and the flipped way the device took.
ROUND_COUNTS = (("map", "maxSerPairs"), ("map", "maxAccPairs"), ("map", "numSpills"),
                ("reduce", "numSegInShuffleFile"), ("reduce", "numShuffleFiles"),
                ("reduce", "numShuffleMerges"), ("reduce", "numSegmentsEvicted"),
                ("reduce", "filesToMergeStep2"), ("reduce", "filesToMergeStep3"))


@functools.lru_cache(maxsize=None)
def _kinds(cls) -> tuple:
    return tuple((f.name, type(f.default)) for f in dataclasses.fields(cls))


def _coerce(cls, values: dict):
    """Build one of the parameter dataclasses from floats: int and bool
    fields are rounded the way a flat float assignment is meant."""
    kw = {}
    for name, kind in _kinds(cls):
        if name in values:
            v = values[name]
            kw[name] = (int(round(v)) if kind is int
                        else v > 0.5 if kind is bool else float(v))
    return cls(**kw)


class Job:
    """One job deployment with the seed's cost factors."""

    def __init__(self, cfg: dict, seed: int):
        self.cfg = cfg
        rng = np.random.default_rng(seed)
        lo, hi = cfg["cost_factor_scale"]
        keys = sorted(cfg["costs"])
        scale = np.exp(rng.uniform(math.log(lo), math.log(hi), size=len(keys)))
        self.costs = {k: cfg["costs"][k] * float(s) for k, s in zip(keys, scale)}
        self.params = dict(cfg["params"])
        self.stats = dict(cfg["stats"])
        self.grid = {k: [float(x) for x in v] for k, v in cfg["grid"].items()}
        self._s = _coerce(ref_params.ProfileStats, self.stats)
        self._c = _coerce(ref_params.CostFactors, self.costs)

    def program_spec(self):
        """The base job as the program's :class:`repro.spec.JobSpec`."""
        from repro.core.hadoop.params import CostFactors, HadoopParams, ProfileStats
        from repro.spec import JobSpec

        return JobSpec(_coerce(HadoopParams, self.params),
                       _coerce(ProfileStats, self.stats),
                       _coerce(CostFactors, self.costs))

    def oracle(self, row: dict, flip=frozenset()):
        """``(cost, valid, edge decisions, round counts)`` of one row: Eq. 98
        and the closed form's domain."""
        p = _coerce(ref_params.HadoopParams, {**self.params, **row})
        j, at_edge = edges(p, self._s, self._c, flip)
        f2 = p.pSortFactor ** 2
        counts = [j.map.numSpills]
        if p.pNumReducers > 0:
            counts += [j.reduce.filesToMergeStep2, j.reduce.filesToMergeStep3]
        valid = all(n <= f2 for n in counts)
        rounds = tuple(getattr(getattr(j, part), name) for part, name in ROUND_COUNTS)
        return j.totalCost, bool(valid), at_edge, rounds

    def variants(self, row: dict):
        """``(cost, valid, round counts)`` of the row under every way its
        edge decisions can go, the oracle's own way first."""
        cost, valid, at_edge, rounds = self.oracle(row)
        out = [(cost, valid, rounds)]
        n = min(len(at_edge), MAX_FLIPPED_EDGES)
        for r in range(1, n + 1):
            for flip in itertools.combinations(range(n), r):
                c, v, _, rr = self.oracle(row, frozenset(flip))
                out.append((c, v, rr))
        return out


class OracleRows:
    """Accumulates device rows against the oracle.

    A row agrees where some way its edge decisions can go (the oracle's own,
    or any set of them flipped) gives the device's valid flag and, where
    valid, a cost within :data:`ORACLE_RTOL`.  ``rel_err`` is the largest
    error of the best such variant over rows whose valid flag some variant
    matches; ``valid_mismatch`` counts the rows that no variant matches."""

    def __init__(self, job: Job):
        self.job = job
        self.rows = 0
        self.flipped = 0
        self.rel_err = 0.0
        self.valid_mismatch = 0
        self.worst: list[str] = []
        self.edge_rows: list[str] = []

    def add(self, row: dict, cost: float, valid: bool) -> None:
        cost, valid = float(cost), bool(valid)
        self.rows += 1
        best = math.inf
        variants = self.job.variants(row)
        for i, (c, v, _) in enumerate(variants):
            if v != valid:
                continue
            err = abs(cost - c) / c if v else 0.0
            if err < best:
                best, which = err, i
        if best == math.inf:
            self.valid_mismatch += 1
            self.worst.append(f"row {row}: valid {valid}, no oracle variant agrees")
            return
        if which > 0:
            self.flipped += 1
            own, took = variants[0][2], variants[which][2]
            moved = "; ".join(f"{name} {a!r} (oracle) vs {b!r} (device)"
                              for (_, name), a, b in zip(ROUND_COUNTS, own, took) if a != b)
            self.edge_rows.append(f"edge row {row}: {moved or 'a comparison flipped'}; "
                                  f"valid {valid}, cost rel err {best!r} against "
                                  f"the flipped oracle")
        if best > self.rel_err:
            self.rel_err = best
            if best > ORACLE_RTOL:
                self.worst.append(f"row {row}: cost {cost!r} vs oracle (rel {best!r})")


def row_flags(task) -> np.ndarray:
    """Per row of the columns of ``task = (cfg, seed, cols, thr)``: valid by
    the oracle's own way, valid under every way its edge decisions can go,
    valid under some way, and valid and cheaper than ``thr`` under every way.
    A worker's task: it builds the seed's job anew."""
    cfg, seed, cols, thr = task
    job = Job(cfg, seed)
    n = len(next(iter(cols.values())))
    out = np.zeros((n, 4), dtype=bool)
    for i in range(n):
        row = {k: float(v[i]) for k, v in cols.items()}
        cost, valid, at_edge, rounds = job.oracle(row)
        ways = job.variants(row) if at_edge else [(cost, valid, rounds)]
        out[i] = (valid, all(v for _, v, _ in ways), any(v for _, v, _ in ways),
                  all(v and c < thr for c, v, _ in ways))
    return out
