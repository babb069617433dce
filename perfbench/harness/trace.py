"""The profiler trace of a traced run, reduced to what the per-layer metrics
read: device busy time, time per program (HLO module) and per operation, and
the idle gaps named by what the host was doing.

The reduction works on plain event records ``(plane, line, name, start_ns,
duration_ns)``, so it can be checked on a small recorded trace without a
chip.  Programs are found by their HLO module name as XLA reports it on the
device's ``XLA Modules`` line; operations on its ``XLA Ops`` line; host spans
are the harness's own ``perfbench:<name>`` annotations."""

from __future__ import annotations

import glob
import os
import re
import shutil
from collections import defaultdict

__all__ = ["read_events", "reduce_events", "module_base", "program_time",
           "ProgramNotFound"]

OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
HOST_PREFIX = "perfbench:"


def read_events(logdir: str) -> list[tuple]:
    """Event records of the newest ``.xplane.pb`` under ``logdir``; the
    directory is removed once read (traces are large)."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not paths:
        return []
    pd = ProfileData.from_file(paths[-1])
    out = []
    for plane in pd.planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for e in line.events:
                if device or e.name.startswith(HOST_PREFIX):
                    out.append((plane.name, line.name, e.name,
                                float(e.start_ns), float(e.duration_ns)))
    shutil.rmtree(logdir, ignore_errors=True)
    return out


def module_base(name: str) -> str:
    """``jit_per_device(12)`` -> ``jit_per_device``."""
    return re.sub(r"\(\d+\)$", "", name).strip()


def _union(intervals):
    """Sorted, merged ``[start, end)`` intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(iv, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


def reduce_events(events: list[tuple]) -> dict | None:
    """Busy and idle time of the devices over the ``perfbench:window`` span,
    time per program and per operation, and idle time by host activity.
    ``None`` when the trace holds no window or no device operation."""
    windows = [(s, s + d) for p, _, n, s, d in events if n == HOST_PREFIX + "window"]
    if not windows:
        return None
    lo, hi = windows[0]
    ops = defaultdict(list)
    modules, op_time = defaultdict(lambda: [0, 0.0]), defaultdict(float)
    for plane, line, name, s, d in events:
        if not plane.startswith("/device:") or s + d <= lo or s >= hi:
            continue
        if line == OPS_LINE:
            ops[plane].append((s, s + d))
            op_time[name] += d * 1e-9
        elif line == MODULES_LINE:
            m = modules[module_base(name)]
            m[0] += 1
            m[1] += d * 1e-9
    if not ops:
        return None
    busy = {p: _union(_clip(iv, lo, hi)) for p, iv in ops.items()}
    busy_s = sum(sum(e - s for s, e in iv) for iv in busy.values()) / len(busy) * 1e-9
    # idle gaps of the first device, each charged to the host spans over it
    first = busy[sorted(busy)[0]]
    gaps, t = [], lo
    for s, e in first:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if t < hi:
        gaps.append((t, hi))
    # innermost spans first: time under a nested span is its own, not its parent's
    host = sorted(((n[len(HOST_PREFIX):], s, s + d) for p, _, n, s, d in events
                   if p.startswith("/host") and n.startswith(HOST_PREFIX)
                   and n != HOST_PREFIX + "window"), key=lambda h: h[2] - h[1])
    idle = defaultdict(float)
    for gs, ge in gaps:
        left = [(gs, ge)]
        for name, s, e in host:
            if e <= gs or s >= ge:
                continue
            nxt = []
            for a, b in left:
                cs, ce = max(a, s), min(b, e)
                if ce > cs:
                    idle["host in " + name] += (ce - cs) * 1e-9
                    nxt += [iv for iv in ((a, cs), (ce, b)) if iv[1] > iv[0]]
                else:
                    nxt.append((a, b))
            left = nxt
        rest = sum(b - a for a, b in left)
        if rest > 0:
            idle["host outside harness spans"] += rest * 1e-9
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy_s,
        "devices": len(busy),
        "modules": {k: tuple(v) for k, v in modules.items()},
        "device_ops": sorted(op_time.items(), key=lambda kv: -kv[1])[:10],
        "idle_gaps": sorted(idle.items(), key=lambda kv: -kv[1])[:10],
    }


class ProgramNotFound(RuntimeError):
    """A traced run's window does not hold the program a metric reads as
    often as the harness called it."""


def program_time(run: dict, module: str, span: str) -> tuple[int, float]:
    """(calls, device seconds) of the program named ``module`` in a traced
    run's window, which has to run once per chip for each host span named
    ``span``.  Raises :class:`ProgramNotFound` otherwise, so that a program
    renamed, shared with another call or missing fails the run instead of
    dropping or inflating its metric."""
    tr = run["trace"]
    calls, seconds = tr["modules"].get(module, (0, 0.0))
    want = sum(1 for n, _, _ in run["spans"] if n == span) * tr["devices"]
    if calls != want or want == 0:
        raise ProgramNotFound(
            f"program {module!r} ran {calls} times in the traced window, where "
            f"{want} calls were made ({span!r} spans times chips); programs "
            f"seen (calls, device seconds): {tr['modules']}")
    return calls, seconds
