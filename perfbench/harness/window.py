"""The measured window: host spans around the calls into the program,
compile events counted while the window is open, and the comparisons that
decide ``correct``."""

from __future__ import annotations

import collections
import contextlib
import threading
import time
from dataclasses import dataclass

__all__ = ["CompileCounter", "Spans", "TimedEvaluator", "Check"]


_LISTENING: list["CompileCounter"] = []
_INSTALLED = False


def _on_event(event: str, duration_secs: float, **kwargs) -> None:
    for c in _LISTENING:
        c.record(event)


class CompileCounter:
    """Counts JAX's compile and compile-cache events while armed; there
    should be none inside the window."""

    def __init__(self):
        global _INSTALLED
        self.counts: collections.Counter = collections.Counter()
        if not _INSTALLED:
            from jax import monitoring

            monitoring.register_event_duration_secs_listener(_on_event)
            _INSTALLED = True

    def record(self, event: str) -> None:
        if "compil" in event:
            self.counts[event] += 1

    @contextlib.contextmanager
    def armed(self):
        _LISTENING.append(self)
        try:
            yield self
        finally:
            _LISTENING.remove(self)

    @property
    def compiles(self) -> int:
        return sum(n for e, n in self.counts.items() if "backend_compile" in e)


class Spans:
    """Host spans on ``time.perf_counter``; with ``annotate`` each also goes
    into the profiler's trace as ``perfbench:<name>``, on the device's clock."""

    def __init__(self, annotate: bool = False):
        self.annotate = annotate
        self.records: list[tuple[str, float, float]] = []
        self._lock = threading.Lock()

    @contextlib.contextmanager
    def span(self, name: str):
        t0 = time.perf_counter()
        try:
            if self.annotate:
                import jax

                with jax.profiler.TraceAnnotation("perfbench:" + name):
                    yield
            else:
                yield
        finally:
            t1 = time.perf_counter()
            with self._lock:
                self.records.append((name, t0, t1))

    def between(self, t0: float, t1: float):
        return [r for r in self.records if r[1] >= t0 and r[2] <= t1]


class TimedEvaluator:
    """Delegates to an evaluator of the program, timing each call into it.

    ``chunk_topk`` returns host arrays, so its span ends after the device
    finished.  Each block's result is kept as the timed path produced it, for
    the comparisons after the window.  Only blocks finished by ``deadline``
    count as work done in the window."""

    def __init__(self, inner, spans: Spans):
        self._inner = inner
        self._spans = spans
        self.deadline: float | None = None
        self.blocks: list = []          # (start, rows, BlockTopK) of this search
        self.rows_done = 0              # rows of blocks finished by the deadline
        self.blocks_done = 0

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def begin_search(self) -> None:
        self.blocks = []

    def chunk_topk(self, overrides, k):
        n = len(next(iter(overrides.values())))
        start = self.blocks[-1][0] + self.blocks[-1][1] if self.blocks else 0
        with self._spans.span("chunk_topk"):
            block = self._inner.chunk_topk(overrides, k)
        if self.deadline is None or time.perf_counter() <= self.deadline:
            self.rows_done += n
            self.blocks_done += 1
        self.blocks.append((start, n, block))
        return block

    def evaluate(self, overrides):
        with self._spans.span("evaluate"):
            return self._inner.evaluate(overrides)


@dataclass
class Check:
    """One number compared with its limit: the run is correct only where
    every value is at or below its limit."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit
