"""Spans and a compile tally for the served path.

``span(name)`` times one stretch of work on the host and marks it for the
profiler: it opens a ``jax.profiler.TraceAnnotation("repro/<name>")``, so a
profiled run shows the span on the host plane, on the clock the device ops
are timed by, and it measures its own ``perf_counter`` duration.  Spans nest
on a per-thread stack; a closed span hands its seconds, compiles and
counters up to the span that encloses it, so a caller reads what its callees
did from its own :class:`Span`::

    with obs.span("plan") as sp:
        with obs.span("plan/pack"):
            ...
    sp.seconds, sp.inner("plan/pack"), sp.compiles

The compile tally listens to ``jax.monitoring`` for every XLA executable
built (compiled, or loaded from the persistent cache) and charges it to the
innermost span open on the compiling thread, or to ``"none"``.

Nothing here needs a flag: with no profiler session running an annotation
costs about a microsecond.  Spans sit at per-batch, per-pinned-group and
per-layer grain, never inside a loop over records, rows or reads.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from contextlib import contextmanager
from typing import Dict, Iterator, List

import jax

PREFIX = "repro/"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


@dataclasses.dataclass
class Span:
    """One closed (or still open) span.  ``compiles``, ``compile_s``,
    ``inner_s`` and ``counts`` include every span it enclosed."""

    name: str
    start: float = 0.0  # perf_counter at entry
    seconds: float = 0.0
    compiles: int = 0
    compile_s: float = 0.0
    inner_s: Dict[str, float] = dataclasses.field(default_factory=dict)
    counts: Dict[str, int] = dataclasses.field(default_factory=dict)

    def inner(self, name: str) -> float:
        """Seconds spent in enclosed spans called ``name``, summed."""
        return self.inner_s.get(name, 0.0)

    def _close_into(self, parent: "Span") -> None:
        parent.compiles += self.compiles
        parent.compile_s += self.compile_s
        parent.inner_s[self.name] = parent.inner(self.name) + self.seconds
        for k, v in self.inner_s.items():
            parent.inner_s[k] = parent.inner(k) + v
        for k, v in self.counts.items():
            parent.counts[k] = parent.counts.get(k, 0) + v


_local = threading.local()


def _stack() -> List[Span]:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


@contextmanager
def span(name: str) -> Iterator[Span]:
    """Time the enclosed work as ``repro/<name>``; yields its :class:`Span`,
    whose ``seconds`` is set on exit."""
    s = Span(name)
    stack = _stack()
    stack.append(s)
    try:
        with jax.profiler.TraceAnnotation(PREFIX + name):
            s.start = time.perf_counter()
            try:
                yield s
            finally:
                s.seconds = time.perf_counter() - s.start
    finally:
        stack.pop()
        if stack:
            s._close_into(stack[-1])


def count(name: str, n: int) -> None:
    """Add ``n`` to counter ``name`` of the innermost open span (dropped
    where no span is open)."""
    stack = _stack()
    if stack:
        counts = stack[-1].counts
        counts[name] = counts.get(name, 0) + n


class CompileTally:
    """Executables built in this process, charged to the innermost open
    span by name (``"none"`` where no span was open), and persistent-cache
    hits."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.by_span: Dict[str, List[float]] = {}  # name -> [count, seconds]
        self.compiles = 0
        self.cache_hits = 0

    def _on_duration(self, event: str, duration: float, **kw) -> None:
        if event != COMPILE_EVENT:
            return
        stack = _stack()
        if stack:
            stack[-1].compiles += 1
            stack[-1].compile_s += duration
        name = stack[-1].name if stack else "none"
        with self._lock:
            entry = self.by_span.setdefault(name, [0, 0.0])
            entry[0] += 1
            entry[1] += duration
            self.compiles += 1

    def _on_event(self, event: str, **kw) -> None:
        if event == CACHE_HIT_EVENT:
            with self._lock:
                self.cache_hits += 1


#: the process's one compile tally, registered once at import
TALLY = CompileTally()
jax.monitoring.register_event_duration_secs_listener(TALLY._on_duration)
jax.monitoring.register_event_listener(TALLY._on_event)
