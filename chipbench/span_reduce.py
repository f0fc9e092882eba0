"""Profiler trace → device idle time by program span, device time by scope.

Reads the same ``jax.profiler.ProfileData`` as ``trace_reduce``, and the
program's own marks in it:

* the host spans ``repro/<name>`` that ``repro.obs.span`` opens on the
  served path (graph, plan, plan/build, plan/pack, exec, undo_capture,
  device_put, step, sync, serve_reads, read_gather, read_undo);
* JAX's ``backend_compile_and_load`` annotation around every XLA compile;
* the ``jax.named_scope`` names ``layer<l>/<stage>`` that the fused step
  carries in its HLO ``op_name`` metadata.

Each idle gap of the first chip inside the harness's window is labelled
with the innermost of those host spans that covers most of it: a compile
annotation counts as ``compile``; where no program span is open, the
``chipbench/`` harness span (other than the window) names it; where none
is, ``other``.  Device time of the fused step (the ``jit_fused_stream_step``
programs that started in the window) is summed per ``layer<l>/<stage>``
scope.  A TPU trace's op events carry no op name of their own (their
stats are offsets and durations), so an op's scope comes from the
``op_name`` metadata of its compiled module, which the profiler stores as
an HLO proto on its ``/host:metadata`` plane; an op without one counts as
``unscoped``.
"""
from __future__ import annotations

import bisect
import heapq
import re
from collections import defaultdict
from pathlib import Path
from typing import Dict, Iterator, List, Optional, Tuple

from chipbench.trace_reduce import (
    DEVICE_PLANE,
    MODULES_LINE,
    OPS_LINE,
    TOP,
    WINDOW,
    _short,
    _union,
)

PROGRAM = "repro/"
HARNESS = "chipbench/"
COMPILE_ANNOTATION = "backend_compile_and_load"
STEP_MODULE = "jit_fused_stream_step"
METADATA_PLANE = "/host:metadata"
STAGES = ("messages", "scatter", "delta_agg", "constrained", "update", "halo")
SCOPE = re.compile(r"(?:^|/)(layer\d+)(?:/(%s)(?=/|$))?" % "|".join(STAGES))
UNSCOPED = "unscoped"

Span = Tuple[str, float, float]


# ---------------------------------------------------------------------- #
# host spans
# ---------------------------------------------------------------------- #
def _label(name: str) -> Optional[str]:
    if name.startswith(PROGRAM):
        return name
    if name == COMPILE_ANNOTATION:
        return "compile"
    if name.startswith(HARNESS) and name != WINDOW:
        return name
    return None


def program_spans(pd) -> List[Span]:
    """Every ``repro/`` span, compile annotation and ``chipbench/`` span
    (but the window) on a host plane, as (label, start, end) ns."""
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                label = _label(e.name)
                if label is not None:
                    out.append((label, float(e.start_ns),
                                float(e.start_ns + e.duration_ns)))
    return sorted(out, key=lambda s: (s[1], -s[2]))


def innermost(spans: List[Span]) -> List[Span]:
    """The time line cut where any span starts or ends, each piece labelled
    with the latest-started span still open over it (pieces that no span
    covers are left out)."""
    cuts = sorted({t for _, a, b in spans for t in (a, b)})
    starts = sorted(spans, key=lambda s: s[1])
    heap: List[Tuple[float, float, str]] = []  # (-start, end, label)
    out: List[Span] = []
    k = 0
    for a, b in zip(cuts, cuts[1:]):
        while k < len(starts) and starts[k][1] <= a:
            label, s0, s1 = starts[k]
            heapq.heappush(heap, (-s0, s1, label))
            k += 1
        while heap and heap[0][1] <= a:
            heapq.heappop(heap)
        if heap:
            label = heap[0][2]
            if out and out[-1][0] == label and out[-1][2] == a:
                out[-1] = (label, out[-1][1], b)
            else:
                out.append((label, a, b))
    return out


def label_gaps(gaps: List[Tuple[float, float]], timeline: List[Span]
               ) -> List[Tuple[str, float, float]]:
    """Each gap with the timeline label that covers most of it."""
    starts = [s[1] for s in timeline]
    out = []
    for a, b in gaps:
        cover: Dict[str, float] = defaultdict(float)
        k = max(0, bisect.bisect_right(starts, a) - 1)
        while k < len(timeline) and timeline[k][1] < b:
            label, s0, s1 = timeline[k]
            ov = min(b, s1) - max(a, s0)
            if ov > 0:
                cover[label] += ov
            k += 1
        label = max(cover.items(), key=lambda kv: kv[1])[0] if cover else "other"
        out.append((label, a, b))
    return out


# ---------------------------------------------------------------------- #
# op scopes from the compiled modules' HLO (protobuf wire format)
# ---------------------------------------------------------------------- #
def _varint(buf, pos: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[pos]
        pos += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, pos
        shift += 7


def _fields(buf) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one message: ints for varints, memoryviews
    for length-delimited fields; fixed-width fields are skipped."""
    pos, end = 0, len(buf)
    while pos < end:
        key, pos = _varint(buf, pos)
        field, wire = key >> 3, key & 7
        if wire == 0:
            val, pos = _varint(buf, pos)
            yield field, val
        elif wire == 2:
            n, pos = _varint(buf, pos)
            yield field, buf[pos:pos + n]
            pos += n
        elif wire == 1:
            pos += 8
        elif wire == 5:
            pos += 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")


def _str(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _hlo_op_names(hlo_proto) -> Dict[str, str]:
    """Instruction name → ``op_name`` metadata over every computation of
    an ``xla.HloProto`` (hlo_module=1 → computations=3 → instructions=2;
    instruction name=1, metadata=7 → op_name=2)."""
    out = {}
    for f, module in _fields(hlo_proto):
        if f != 1:
            continue
        for f2, comp in _fields(module):
            if f2 != 3:
                continue
            for f3, ins in _fields(comp):
                if f3 != 2:
                    continue
                name = op_name = None
                for f4, v in _fields(ins):
                    if f4 == 1:
                        name = _str(v)
                    elif f4 == 7:
                        for f5, w in _fields(v):
                            if f5 == 2:
                                op_name = _str(w)
                if name and op_name:
                    out[name] = op_name
    return out


def module_op_names(xspace: bytes, prefix: str = STEP_MODULE
                    ) -> Dict[str, Dict[str, str]]:
    """For each compiled module named ``prefix(...)`` on the profiler's
    ``/host:metadata`` plane: instruction name → ``op_name`` metadata.
    (XSpace planes=1; XPlane name=2, event_metadata=4 as map entries
    key=1, value=2; XEventMetadata name=2, stats=5; XStat bytes_value=6.)"""
    buf = memoryview(xspace)
    out: Dict[str, Dict[str, str]] = {}
    for f, plane in _fields(buf):
        if f != 1:
            continue
        fields = list(_fields(plane))
        if not any(k == 2 and _str(v) == METADATA_PLANE for k, v in fields):
            continue
        for k, entry in fields:
            if k != 4:
                continue
            for k2, meta in _fields(entry):
                if k2 != 2:
                    continue
                name, protos = "", []
                for k3, v in _fields(meta):
                    if k3 == 2:
                        name = _str(v)
                    elif k3 == 5:
                        protos += [w for k4, w in _fields(v) if k4 == 6]
                if name.startswith(prefix):
                    for p in protos:
                        out.setdefault(name, {}).update(_hlo_op_names(p))
    return out


def scope_of(op_name: Optional[str]) -> str:
    """``jit(f)/layer1/scatter/scatter-add`` → ``layer1/scatter``."""
    m = SCOPE.search(op_name or "")
    if m is None:
        return UNSCOPED
    return m.group(1) + ("/" + m.group(2) if m.group(2) else "")


# ---------------------------------------------------------------------- #
def reduce_trace(pd, op_names: Optional[Dict[str, Dict[str, str]]] = None
                 ) -> Optional[dict]:
    """Idle gaps of the first chip by program span, and the fused step's
    device time by scope, over the harness's window.  ``op_names`` maps a
    compiled module's name to its instructions' ``op_name`` metadata (see
    :func:`module_op_names`).  ``None`` where the trace holds no window or
    no device plane."""
    windows = [(float(e.start_ns), float(e.start_ns + e.duration_ns))
               for plane in pd.planes if plane.name.startswith("/host:")
               for line in plane.lines for e in line.events
               if e.name == WINDOW]
    devices = sorted((p for p in pd.planes if DEVICE_PLANE.match(p.name)),
                     key=lambda p: p.name)
    if not windows or not devices:
        return None
    w0, w1 = windows[0]
    op_names = op_names or {}
    by_short: Dict[str, Dict[str, str]] = defaultdict(dict)
    for mod, names in op_names.items():
        by_short[_short(mod)].update(names)

    lines = list(devices[0].lines)
    ops = [ln for ln in lines if ln.name == OPS_LINE] or lines
    mods = sorted((float(e.start_ns), float(e.start_ns + e.duration_ns),
                   e.name.strip())
                  for ln in lines if ln.name == MODULES_LINE for e in ln.events)
    mstarts = [m[0] for m in mods]
    busy, step_runs = [], set()
    scope_ns: Dict[str, float] = defaultdict(float)
    for ln in ops:
        for e in ln.events:
            a, b = float(e.start_ns), float(e.start_ns + e.duration_ns)
            if b > w0 and a < w1:
                busy.append((max(a, w0), min(b, w1)))
            k = bisect.bisect_right(mstarts, a) - 1
            if k < 0 or a >= mods[k][1]:
                continue
            m0, _, mod = mods[k]
            if _short(mod) != STEP_MODULE or not (w0 <= m0 < w1):
                continue
            step_runs.add(m0)
            op = _short(e.name)
            name = op_names.get(mod, {}).get(op) or by_short[STEP_MODULE].get(op)
            scope_ns[scope_of(name)] += b - a
    gaps, t = [], w0
    for a, b in _union(busy):
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if w1 > t:
        gaps.append((t, w1))
    named = label_gaps(gaps, innermost(program_spans(pd)))
    idle_by: Dict[str, float] = defaultdict(float)
    for label, a, b in named:
        idle_by[label] += (b - a) * 1e-9
    longest = sorted(named, key=lambda g: g[1] - g[2])[:TOP]
    step_ns = sum(scope_ns.values())
    return {
        "idle_by_span": dict(sorted(idle_by.items(), key=lambda kv: -kv[1])),
        "idle_gaps": [[label, (b - a) * 1e-9] for label, a, b in longest],
        "step_runs": len(step_runs),
        "step_device_s": step_ns * 1e-9,
        "scope_device_s": {k: v * 1e-9 for k, v in
                           sorted(scope_ns.items(), key=lambda kv: -kv[1])},
        "scoped_share": (1.0 - scope_ns.get(UNSCOPED, 0.0) / step_ns
                         if step_ns else None),
    }


def reduce_dir(trace_dir) -> Optional[dict]:
    """:func:`reduce_trace` of the newest ``*.xplane.pb`` under
    ``trace_dir``, with the op-name metadata of its compiled modules."""
    from jax.profiler import ProfileData

    files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not files:
        return None
    raw = files[-1].read_bytes()
    return reduce_trace(ProfileData.from_serialized_xspace(raw),
                        module_op_names(raw))
