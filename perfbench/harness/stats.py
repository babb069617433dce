"""Percentile arithmetic: a copy of ``repro.obs.metrics.percentile_interp``
(linear interpolation between order statistics, numpy's default method),
kept with the benchmark so that the yardstick does not move with the
program."""

from __future__ import annotations

import math
from typing import Sequence

__all__ = ["percentile", "spread", "apportion"]


def percentile(ordered: Sequence[float], p: float) -> float:
    """Linear-interpolated percentile of an already-sorted sequence; empty
    -> 0.0, one sample -> that sample, equal neighbours (inf included) ->
    their common value."""
    vals = list(ordered)
    n = len(vals)
    if n == 0:
        return 0.0
    if n == 1 or p <= 0.0:
        return float(vals[0])
    if p >= 100.0:
        return float(vals[-1])
    rank = (n - 1) * (p / 100.0)
    lo = math.floor(rank)
    frac = rank - lo
    a = float(vals[lo])
    if frac == 0.0:
        return a
    b = float(vals[min(lo + 1, n - 1)])
    return a if a == b else a + (b - a) * frac


def spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartile as a share of the
    median (``statistics.quantiles(values, n=4)``)."""
    import statistics

    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / q2


def apportion(weights: Sequence[float], n: int) -> list[int]:
    """Whole counts summing to ``n`` in proportion to ``weights`` (largest
    remainder, ties to the earlier weight)."""
    total = float(sum(weights))
    raw = [w / total * n for w in weights]
    out = [int(r) for r in raw]
    for i in sorted(range(len(raw)), key=lambda i: out[i] - raw[i])[: n - sum(out)]:
        out[i] += 1
    return out
