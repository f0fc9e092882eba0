"""Profiler trace → device busy time, top device ops and idle gaps.

Reads what ``jax.profiler.ProfileData`` gives (planes → lines → events with
``name``, ``start_ns`` and ``duration_ns`` on one clock).  Device planes are
``/device:TPU:<i>`` (or GPU); on each, the ``XLA Ops`` line holds one event
per operation run.  The harness marks its own host spans with
``jax.profiler.TraceAnnotation`` names under ``chipbench/``: the measured
window, each ``serve_reads`` call, each ``apply_batch`` call and each sleep
until the next due arrival.  An ``apply_batch`` span is split into the
program's graph, plan and exec phases from that batch's ``BatchStats``
durations, laid end to end so that exec ends where the call ends.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

PREFIX = "chipbench/"
WINDOW = PREFIX + "window"
APPLY = PREFIX + "apply_batch"
SERVE = PREFIX + "serve_reads"
SLEEP = PREFIX + "sleep"
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10

Interval = Tuple[float, float]


def _union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _clip(a: float, b: float, lo: float, hi: float) -> Optional[Interval]:
    a, b = max(a, lo), min(b, hi)
    return (a, b) if b > a else None


def host_spans(pd) -> List[Tuple[str, float, float]]:
    """Every ``chipbench/`` span on a host plane, as (name, start, end) ns."""
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX):
                    out.append((e.name, float(e.start_ns),
                                float(e.start_ns + e.duration_ns)))
    return sorted(out, key=lambda s: s[1])


def _short(name: str) -> str:
    """``%fusion.18 = f32[...] fusion(...)`` → ``fusion.18``;
    ``jit_step(123)`` → ``jit_step``."""
    return name.split(" = ", 1)[0].lstrip("%").split("(", 1)[0]


def device_events(pd) -> Dict[str, List[Tuple[str, float, float]]]:
    """Per device plane, its operations as (``module/op``, start, end) ns,
    each op named with the program (``XLA Modules`` line) it ran in."""
    out = {}
    for plane in pd.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        lines = list(plane.lines)
        ops = [ln for ln in lines if ln.name == OPS_LINE] or lines
        mods = sorted((float(e.start_ns), float(e.start_ns + e.duration_ns),
                       _short(e.name))
                      for ln in lines if ln.name == MODULES_LINE
                      for e in ln.events)
        starts = [m[0] for m in mods]
        evs = []
        for ln in ops:
            for e in ln.events:
                a, b = float(e.start_ns), float(e.start_ns + e.duration_ns)
                k = bisect.bisect_right(starts, a) - 1
                mod = mods[k][2] + "/" if k >= 0 and a < mods[k][1] else ""
                evs.append((mod + _short(e.name), a, b))
        out[plane.name] = evs
    return out


def labelled_spans(spans, splits: Sequence[Tuple[float, float, float]]):
    """Host activity as (label, start, end): serve_reads, sleep, and each
    apply_batch cut into graph, plan and exec (``splits`` gives each
    batch's three durations in seconds, in call order)."""
    out = []
    applies = [s for s in spans if s[0] == APPLY]
    for k, (_, a, b) in enumerate(applies):
        if k >= len(splits):
            out.append(("apply_batch", a, b))
            continue
        g, p, x = (v * 1e9 for v in splits[k])
        t_exec = max(a, b - x)
        t_plan = max(a, t_exec - p)
        t_graph = max(a, t_plan - g)
        out += [("exec", t_exec, b), ("plan", t_plan, t_exec),
                ("graph", t_graph, t_plan)]
        if t_graph > a:
            out.append(("apply_batch", a, t_graph))
    for name, a, b in spans:
        if name in (SERVE, SLEEP):
            out.append((name[len(PREFIX):], a, b))
    return out


def reduce_trace(pd, splits: Sequence[Tuple[float, float, float]] = ()) -> dict:
    """Busy and idle time of the device over the harness's window span.

    Returns ``None`` where the trace holds no window span or no device
    plane: a CPU run has no device time to report."""
    spans = host_spans(pd)
    windows = [s for s in spans if s[0] == WINDOW]
    devices = device_events(pd)
    if not windows or not devices:
        return None
    _, w0, w1 = windows[0]
    window_ns = w1 - w0
    busy_per_chip = []
    op_time: Dict[str, float] = defaultdict(float)
    gaps: List[Interval] = []
    for k, (plane, events) in enumerate(sorted(devices.items())):
        clipped = []
        for name, a, b in events:
            c = _clip(a, b, w0, w1)
            if c is not None:
                clipped.append(c)
                op_time[name] += c[1] - c[0]
        busy = _union(clipped)
        busy_per_chip.append(sum(b - a for a, b in busy))
        if k == 0:  # gaps are read on the first chip
            t = w0
            for a, b in busy:
                if a > t:
                    gaps.append((t, a))
                t = max(t, b)
            if w1 > t:
                gaps.append((t, w1))
    host = labelled_spans(spans, splits)
    named_gaps = []
    idle_by: Dict[str, float] = defaultdict(float)
    for a, b in gaps:
        best, label = 0.0, "other"
        for name, s0, s1 in host:
            ov = min(b, s1) - max(a, s0)
            if ov > best:
                best, label = ov, name
        named_gaps.append((label, (b - a) * 1e-9))
        idle_by[label] += (b - a) * 1e-9
    named_gaps.sort(key=lambda g: -g[1])
    ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "busy_s": sum(busy_per_chip) / len(busy_per_chip) * 1e-9,
        "window_s": window_ns * 1e-9,
        "device_ops": [[name, t * 1e-9] for name, t in ops],
        "idle_gaps": [[name, s] for name, s in named_gaps[:TOP]],
        "idle_by_span": dict(sorted(idle_by.items(), key=lambda kv: -kv[1])),
    }


def load(path) -> object:
    from jax.profiler import ProfileData

    return ProfileData.from_file(str(path))
