"""Plain references for a streamed top-k search: the grid's flat index order,
the merge of per-block winners, and the checks of one search's result
against the blocks the timed path produced."""

from __future__ import annotations

import numpy as np

__all__ = ["row_at", "block_rows", "merge_mismatch", "same_entries"]


def row_at(grid: dict, i: int) -> dict:
    """The assignment at flat index ``i`` (C order: last key fastest)."""
    out = {}
    for k in reversed(list(grid)):
        vals = grid[k]
        i, r = divmod(i, len(vals))
        out[k] = float(vals[r])
    return {k: out[k] for k in grid}


def block_rows(grid: dict, start: int, n: int) -> dict:
    """Columns of flat indices ``[start, start + n)``."""
    shape = tuple(len(v) for v in grid.values())
    idx = np.unravel_index(np.arange(start, start + n), shape)
    return {k: np.asarray(v, dtype=np.float64)[i] for (k, v), i in zip(grid.items(), idx)}


def merge_mismatch(grid: dict, blocks: list, entries: list, k: int) -> int:
    """Positions where the search's ranked entries differ from a plain merge
    of its blocks' valid winners (cost, then the lower flat index)."""
    costs, gidx = [], []
    for start, _, b in blocks:
        keep = np.isfinite(b.costs)
        costs.append(np.asarray(b.costs, dtype=np.float64)[keep])
        gidx.append(start + np.asarray(b.idx)[keep].astype(np.int64))
    costs, gidx = np.concatenate(costs), np.concatenate(gidx)
    order = np.lexsort((gidx, costs))[:k]
    want = [(int(gidx[o]), float(costs[o]), row_at(grid, int(gidx[o]))) for o in order]
    got = [(e.index, e.cost, e.assignment) for e in entries if e.valid and not e.exact]
    bad = sum(1 for a, b in zip(want, got) if a != b)
    return bad + abs(len(want) - len(got))


def same_entries(a, b) -> bool:
    return [(e.index, e.cost, e.valid) for e in a.entries] == \
        [(e.index, e.cost, e.valid) for e in b.entries]
