"""repro.obs — metrics, tracing, and profiling hooks for the whole stack.

The paper models a MapReduce job phase-by-phase so costs can be attributed;
this package does the same for the system that reproduces it.  One ambient
:class:`Observability` (a :class:`~repro.obs.metrics.MetricsRegistry` plus a
:class:`~repro.obs.trace.Tracer`) is visible to every instrumented
component via :func:`current`:

    import repro.api as api

    with api.observe(trace="run.json") as ob:
        svc.submit(...)                       # spans + counters recorded
    print(ob.registry.snapshot())             # {"service.queries": 42, ...}
    # run.json opens at https://ui.perfetto.dev

Instrumented code times its host work with :meth:`Observability.span`: a
tracer span (which also lands in any ``jax.profiler`` capture as
``repro:<name>``, on the device's clock) whose duration is one sample of
the registry histogram ``<name>_s``.

Off by default: :func:`current` returns null singletons until an
:func:`observe` context installs live ones, and every instrumented hot path
guards on ``ob.enabled``, so the disabled cost is one attribute check.
Instrumentation is strictly host-side — it never runs inside jitted code
and never changes what an instrumented component computes (CI asserts the
instrumented :class:`~repro.search.evaluator.ChunkedEvaluator` is
bit-for-bit identical to the uninstrumented one).

The ambient slot is process-global, *not* thread-local, on purpose: the
what-if service and serve-loop do their work on worker threads that must
see the ``observe()`` installed by the driving thread.
"""

from __future__ import annotations

import contextlib
import time
from typing import Iterator

from repro.obs.metrics import (
    NULL_REGISTRY,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    percentile_interp,
)
from repro.obs.profile_hooks import install_compile_listener
from repro.obs.trace import _NULL_SPAN, NULL_TRACER, Tracer

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "NULL_REGISTRY",
    "NULL_TRACER",
    "Observability",
    "Tracer",
    "current",
    "observe",
    "percentile_interp",
]


class Observability:
    """A registry + tracer pair; what instrumented components consume."""

    __slots__ = ("registry", "tracer")

    def __init__(self, registry: MetricsRegistry, tracer: Tracer):
        self.registry = registry
        self.tracer = tracer

    @property
    def enabled(self) -> bool:
        return self.registry.enabled or self.tracer.enabled

    def span(self, name: str, **args):
        """``with ob.span("evaluator.fetch"): ...`` — a tracer span whose
        duration is also recorded in the histogram ``<name>_s``.  The
        shared no-op span while observability is off."""
        if not self.enabled:
            return _NULL_SPAN
        return _TimedSpan(self, name, args)


class _TimedSpan:
    """One :meth:`Observability.span`: the tracer's span around the block,
    and its ``perf_counter`` duration into the registry on exit."""

    __slots__ = ("_ob", "_name", "_span", "_t0")

    def __init__(self, ob: Observability, name: str, args: dict):
        self._ob = ob
        self._name = name
        self._span = ob.tracer.span(name, **args)

    def __enter__(self) -> "_TimedSpan":
        self._span.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        dt = time.perf_counter() - self._t0
        self._span.__exit__(*exc)
        self._ob.registry.histogram(self._name + "_s").record(dt)


#: the ambient null default — ``current() is NULL_OBS`` means "off".
NULL_OBS = Observability(NULL_REGISTRY, NULL_TRACER)

_current: Observability = NULL_OBS


def current() -> Observability:
    """The ambient :class:`Observability` (null singletons when off)."""
    return _current


@contextlib.contextmanager
def observe(
    trace: str | None = None,
    *,
    registry: MetricsRegistry | None = None,
    tracer: Tracer | None = None,
) -> Iterator[Observability]:
    """Install a live ambient Observability for the duration of the block.

    ``trace="out.json"`` writes a Chrome trace-event file on exit (open it
    at https://ui.perfetto.dev).  Pass an explicit ``registry``/``tracer``
    to reuse existing instances (e.g. to accumulate across blocks); omitted
    ones are created fresh.  Restores the previous ambient value on exit,
    so contexts nest.  Installs the process-wide ``jax.monitoring`` compile
    listener (:func:`install_compile_listener`), so compiles inside the
    block are counted as ``jax.backend_compile_duration``.
    """
    global _current
    install_compile_listener()
    ob = Observability(
        registry if registry is not None else MetricsRegistry(),
        tracer if tracer is not None else Tracer(),
    )
    prev = _current
    _current = ob
    try:
        yield ob
    finally:
        _current = prev
        if trace is not None:
            ob.tracer.write(trace)


def __getattr__(name: str):
    # Lazy: destrace pulls in repro.cluster (jax) — not for the stdlib-only
    # import path above.
    if name == "workload_trace":
        from repro.obs.destrace import workload_trace

        return workload_trace
    raise AttributeError(f"module 'repro.obs' has no attribute {name!r}")
