"""The program's own spans and counters in a traced run: ``repro.obs``'s live
tracer writes each span into the profiler's trace as ``repro:<name>``, on the
device's clock, and its registry keeps a histogram ``<name>_s`` per span and
the counters.

:func:`reduce_events` lets the program's spans take part in the idle
attribution of :func:`perfbench.harness.trace.reduce_events` beside the
harness's own, innermost first, under ``host in <name>``; on a trace with no
program span it returns exactly what that function returns."""

from __future__ import annotations

import glob
import os
import shutil

from perfbench.harness import trace

__all__ = ["PROGRAM_PREFIX", "read_events", "reduce_events", "program_record"]

PROGRAM_PREFIX = "repro:"


def read_events(logdir: str) -> list[tuple]:
    """Event records of the newest ``.xplane.pb`` under ``logdir``, as
    :func:`perfbench.harness.trace.read_events` gives them, with the
    program's host spans kept too; the directory is removed once read."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not paths:
        return []
    pd = ProfileData.from_file(paths[-1])
    host = (trace.HOST_PREFIX, PROGRAM_PREFIX)
    out = []
    for plane in pd.planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            if device and line.name not in (trace.OPS_LINE, trace.MODULES_LINE):
                continue
            for e in line.events:
                if device or e.name.startswith(host):
                    out.append((plane.name, line.name, e.name,
                                float(e.start_ns), float(e.duration_ns)))
    shutil.rmtree(logdir, ignore_errors=True)
    return out


def reduce_events(events: list[tuple]) -> dict | None:
    """:func:`perfbench.harness.trace.reduce_events` with each program span
    charged like a harness span of the same name."""
    n = len(PROGRAM_PREFIX)
    return trace.reduce_events([
        (p, line, trace.HOST_PREFIX + name[n:] if name.startswith(PROGRAM_PREFIX) else name, s, d)
        for p, line, name, s, d in events])


def program_record(registry) -> dict:
    """``{"histograms": {name: samples}, "counters": {name: value}}`` of a
    ``repro.obs`` registry (gauges among the counters), as the readers of
    the program's spans and counters take it."""
    out = {"histograms": {}, "counters": {}}
    for name, value in registry.snapshot().items():
        if isinstance(value, dict):
            out["histograms"][name] = registry.histogram(name).samples()
        else:
            out["counters"][name] = value
    return out
