"""Merge-round mathematics (paper §2.3, Eqs. 20-22) for the float64 oracle.

A copy of the pure-Python part of ``src/repro/core/hadoop/merge_math.py``,
kept with the benchmark so that the reference imports nothing of the program.

Hadoop merges ``N`` sorted spill files with an external multi-pass merge of
fan-in ``F`` (= ``io.sort.factor``).  The first pass is sized so that every
later intermediate pass merges exactly ``F`` files.  The paper's closed forms
hold for ``N <= F**2``; beyond that the merge loop is simulated.
"""

from __future__ import annotations

import heapq
from dataclasses import dataclass

__all__ = ["MergePlan", "merge_plan"]


def calc_num_spills_first_pass(n: int, f: int) -> int:
    """Eq. 20 — number of spills merged by the first merge pass."""
    if n <= f:
        return n
    if (n - 1) % (f - 1) == 0:
        return f
    return (n - 1) % (f - 1) + 1


def calc_num_spills_interm_merge(n: int, f: int) -> int:
    """Eq. 21 — spill-equivalents read during first + intermediate passes."""
    if n <= f:
        return 0
    p = calc_num_spills_first_pass(n, f)
    return p + ((n - p) // f) * f


def calc_num_spills_final_merge(n: int, f: int) -> int:
    """Eq. 22 — number of streams merged by the final merge pass."""
    if n <= f:
        return n
    p = calc_num_spills_first_pass(n, f)
    s = calc_num_spills_interm_merge(n, f)
    return 1 + (n - p) // f + (n - s)


def num_merge_passes(n: int, f: int) -> int:
    """Eq. 25 — total number of merge passes (incl. first and final)."""
    if n <= 1:
        return 0
    if n <= f:
        return 1
    p = calc_num_spills_first_pass(n, f)
    return 2 + (n - p) // f


@dataclass(frozen=True)
class MergePlan:
    """Full accounting of a multi-pass merge of ``n`` unit-weight spills."""

    n: int
    f: int
    first_pass: int
    interm_reads: float
    final_merge_width: int
    passes: int


def simulate_merge(n: int, f: int) -> MergePlan:
    """Hadoop's merge loop for any ``n``: the first pass merges
    :func:`calc_num_spills_first_pass` of the smallest files, every later
    pass the ``f`` smallest, until at most ``f`` remain for the final merge."""
    if n <= 1:
        return MergePlan(n, f, 0, 0.0, n, 0)
    if n <= f:
        return MergePlan(n, f, n, 0.0, n, 1)
    heap: list[float] = [1.0] * int(n)
    heapq.heapify(heap)
    p = calc_num_spills_first_pass(n, f)
    merged = sum(heapq.heappop(heap) for _ in range(int(p)))
    interm_reads, passes = merged, 1
    heapq.heappush(heap, merged)
    while len(heap) > f:
        merged = sum(heapq.heappop(heap) for _ in range(int(f)))
        interm_reads += merged
        heapq.heappush(heap, merged)
        passes += 1
    return MergePlan(n, f, p, interm_reads, len(heap), passes + 1)


def merge_plan(n: int, f: int) -> MergePlan:
    """Closed forms when valid (``n <= f**2``), the simulated loop otherwise."""
    if n <= f * f:
        return MergePlan(
            n,
            f,
            calc_num_spills_first_pass(n, f) if n > f else (n if n > 1 else 0),
            float(calc_num_spills_interm_merge(n, f)),
            calc_num_spills_final_merge(n, f) if n > 1 else n,
            num_merge_passes(n, f),
        )
    return simulate_merge(n, f)
