"""Frozen operation and byte counts of the kernels whose roofline share the
benchmark reports, computed from shapes alone so that every implementation
is held to the same work."""

from __future__ import annotations

__all__ = ["JOB_MODEL_FLOPS_PER_ROW", "topk_body_work"]

#: Floating-point operations of the closed-form job model (Eqs. 2-98) for one
#: configuration: the mul, add, sub, div, floor, ceil, log, max, min and rem
#: operations of its traced jaxpr (136 + 94 + 43 + 40 + 16 + 3 + 2 + 9 + 3 +
#: 11), counted once when this benchmark was written.  Selects, compares and
#: conversions are not counted.
JOB_MODEL_FLOPS_PER_ROW = 357


def topk_body_work(rows: int, swept_keys: int) -> tuple[float, float]:
    """(operations, bytes) of one top-k chunk: the job model on every row,
    and the least traffic it needs, one float32 per swept key and one mask
    byte per row read from memory (the 2k winners it writes are noise)."""
    return float(rows * JOB_MODEL_FLOPS_PER_ROW), float(rows * (4 * swept_keys + 1))
