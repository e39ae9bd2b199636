"""Host-side span tracing — Chrome-trace-event JSON, viewable in Perfetto.

``utils.profiling.trace`` captures DEVICE profiles (XProf) and
``jax.named_scope`` names ops inside the compiled graph; neither shows
the HOST timeline — where did the wall clock go between dispatches?
(data loading, eval, snapshot writes, and above all COMPILES: the
dynamic-batch path in ``train/solver.py`` recompiles on every new batch
shape, and without host spans a recompile is a mystery stall.)

``SpanTracer`` records hierarchical host spans as Chrome trace events
("X" complete events keyed by pid/tid; nesting is derived from
timestamp containment, the Chrome/Perfetto convention), plus "i"
instant events for point-in-time markers.  ``write()`` emits the
``{"traceEvents": [...]}`` JSON Perfetto accepts.

One tracer per process: :func:`install` names it, :func:`current`
returns it, and the module-level :func:`span` / :func:`instant` are
what every layer boundary of the program calls.  A span lands (a) in
the installed tracer's buffer, if one is installed, and (b) in the
profiler's own trace as a ``jax.profiler.TraceAnnotation``, if jax is
already imported — so any profiler session (``utils.profiling.trace``,
the benchmark's traced run) holds the program's host spans in the
xplane's host plane, on the clock the device operations are on.  With
neither a tracer nor a session a span costs one small object.

Stdlib only — no top-level jax import (the tracer must work in
jax-free processes: the offline gates, a chip driver's parent); the
annotation class is looked up lazily in ``sys.modules``.
"""

from __future__ import annotations

import contextlib
import json
import os
import sys
import threading
import time
from typing import Any, Callable, Dict, Iterator, List, Optional, Tuple


class SpanTracer:
    """Collects host spans; thread-safe; bounded by ``max_events``.

    Timestamps are microseconds since the tracer's creation (Chrome
    trace ``ts`` is relative anyway); absolute wall time at creation is
    stamped in the trace metadata so events can be correlated with
    metric records' ``wall_time``.  ``origin`` is the creation time on
    ``clock`` (``perf_counter`` seconds by default): an event ends at
    ``origin + (ts + dur) / 1e6``, so a window given in ``perf_counter``
    seconds can clip the buffer.  ``clock``/``wall`` are injectable for
    deterministic tests.
    """

    def __init__(self, max_events: int = 200_000,
                 clock: Optional[Callable[[], float]] = None,
                 wall: Optional[Callable[[], float]] = None):
        self._clock = clock or time.perf_counter
        self.wall = wall or time.time
        self.origin = self._clock()
        self.wall_time_origin = self.wall()
        self._events: List[Dict[str, Any]] = []
        self._lock = threading.Lock()
        self._max_events = max_events
        self._dropped = 0
        self._pid = os.getpid()
        # Fleet identity (obs.fleet.stamp): stamped into the trace
        # metadata so every span in a per-rank trace file is
        # attributable to its rank; None = no fleet block in the
        # output (byte-identical to the pre-fleet trace).
        self.stamp: Optional[Dict[str, Any]] = None

    @property
    def dropped(self) -> int:
        """Events the ``max_events`` cap has eaten so far — consumers
        (solver window rows, serve window rows, the fleet aggregator)
        surface this instead of silently averaging a truncated
        stream."""
        with self._lock:
            return self._dropped

    def now_us(self) -> float:
        """Current tracer-relative timestamp — a cursor consumers can
        compare span timestamps against (e.g. the ``prof`` CLI keeps
        only the spans of its measured loop)."""
        return (self._clock() - self.origin) * 1e6

    def to_us(self, t: float) -> float:
        """A reading of ``clock`` (seconds) as a tracer timestamp."""
        return (t - self.origin) * 1e6

    def events_since(self, index: int) -> "Tuple[List[Dict[str, Any]], int, int]":
        """``(events[index:], next_index, dropped)`` — the incremental
        read for windowed consumers (the serve window rows).  Spans are
        appended at span END, so the tail slice is exactly the spans
        that *finished* since the last read: a span in flight across
        the boundary lands in the next window instead of vanishing
        (filtering a full snapshot by start-``ts`` drops every
        boundary-straddling span — the longest ones).  O(new events)
        per read, not O(whole buffer); ``dropped`` > 0 means the
        ``max_events`` cap is eating spans and the split is partial."""
        with self._lock:
            tail = self._events[index:]
            return tail, index + len(tail), self._dropped

    def _append(self, ev: Dict[str, Any]) -> None:
        with self._lock:
            if len(self._events) >= self._max_events:
                # No silent caps: the drop count is published in the
                # trace metadata (and a truncated trace stays valid).
                self._dropped += 1
                return
            self._events.append(ev)

    def complete_event(self, name: str, t0_us: float, t1_us: float,
                       **args: Any) -> Dict[str, Any]:
        """The "X" event shape, stamped with the calling thread — built
        here for the buffer AND for consumers that keep events of their
        own (obs.qtrace's per-query trees), so both read alike."""
        ev: Dict[str, Any] = {
            "name": name,
            "ph": "X",
            "ts": t0_us,
            "dur": max(t1_us - t0_us, 0.0),
            "pid": self._pid,
            "tid": threading.get_ident() & 0xFFFFFFFF,
        }
        if args:
            ev["args"] = args
        return ev

    def instant_event(self, name: str, **args: Any) -> Dict[str, Any]:
        """The "i" event shape (thread-scoped), stamped now."""
        ev: Dict[str, Any] = {
            "name": name,
            "ph": "i",
            "s": "t",
            "ts": self.now_us(),
            "pid": self._pid,
            "tid": threading.get_ident() & 0xFFFFFFFF,
        }
        if args:
            ev["args"] = args
        return ev

    def span(self, name: str, **args: Any) -> "_Span":
        """``with tracer.span("data/next_batch"): ...`` — one complete
        ("X") event covering the block, in THIS tracer's buffer (and
        the profiler's trace).  Nest freely; Perfetto stacks spans on
        the same thread by timestamp containment."""
        return _Span(name, args, self)

    def instant(self, name: str, **args: Any) -> None:
        """Point-in-time marker ("i" event) — e.g. "recompile"."""
        self._append(self.instant_event(name, **args))

    @property
    def num_events(self) -> int:
        with self._lock:
            return len(self._events)

    def to_chrome_trace(self) -> Dict[str, Any]:
        """The Chrome trace-event JSON object (Perfetto's legacy-JSON
        loader accepts exactly this shape)."""
        with self._lock:
            events = list(self._events)
            dropped = self._dropped
        meta: Dict[str, Any] = {
            "wall_time_origin": self.wall_time_origin,
        }
        if dropped:
            meta["dropped_events"] = dropped
        if self.stamp:
            # Rank identity for every span in this stream: the trace
            # file is per-rank under the fleet path scheme, so a
            # file-level stamp makes each event unambiguous without
            # paying ~30 bytes of args on all 200k of them.
            meta["fleet"] = dict(self.stamp)
        return {
            "traceEvents": events,
            "displayTimeUnit": "ms",
            "otherData": meta,
        }

    def write(self, path: str) -> str:
        """Serialize to ``path`` (atomic: tmp + rename); returns path."""
        path = os.path.abspath(path)
        parent = os.path.dirname(path)
        if parent:
            os.makedirs(parent, exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(self.to_chrome_trace(), f)
        os.replace(tmp, path)
        return path


# -- the process's tracer and the one span function --------------------------

_current: Optional[SpanTracer] = None
_local = threading.local()
_annotation_cls = None


def install(tracer: Optional[SpanTracer]) -> Optional[SpanTracer]:
    """Name the process's tracer (None uninstalls); returns the one it
    replaces.  ``RunTelemetry(trace=True)`` and a ``QueryTracer`` built
    without one call this — one origin and one buffer per process."""
    global _current
    prev, _current = _current, tracer
    return prev


def current() -> Optional[SpanTracer]:
    return _current


def _annotation():
    """``jax.profiler.TraceAnnotation`` once jax is imported, else None
    — never an import of jax from here."""
    global _annotation_cls
    if _annotation_cls is None:
        jax = sys.modules.get("jax")
        prof = getattr(jax, "profiler", None)
        _annotation_cls = getattr(prof, "TraceAnnotation", None)
    return _annotation_cls


@contextlib.contextmanager
def tagged(**tags: Any) -> Iterator[None]:
    """Every span this thread opens inside the block carries ``tags``
    — the shared identifier of one unit of work (a dispatch's
    ``batch``) across layers that do not pass it to each other."""
    prev = getattr(_local, "tags", None)
    _local.tags = {**prev, **tags} if prev else tags
    try:
        yield
    finally:
        _local.tags = prev


def tags() -> Dict[str, Any]:
    """The calling thread's :func:`tagged` arguments."""
    return getattr(_local, "tags", None) or {}


class _Span:
    """Context manager of one span: the profiler's annotation around
    the tracer's clock readings."""

    __slots__ = ("name", "args", "tracer", "_t0", "_ann")

    def __init__(self, name: str, args: Dict[str, Any],
                 tracer: Optional[SpanTracer]):
        shared = getattr(_local, "tags", None)
        self.name = name
        self.args = {**shared, **args} if shared else args
        self.tracer = tracer

    def __enter__(self) -> "_Span":
        cls = _annotation()
        self._ann = cls(self.name, **self.args) if cls is not None else None
        if self._ann is not None:
            self._ann.__enter__()
        if self.tracer is not None:
            self._t0 = self.tracer.now_us()
        return self

    def note(self, **args: Any) -> None:
        """Arguments known only inside the block (how a batch formed).
        They reach the tracer's event, written at exit; the profiler's
        annotation took its arguments at entry and keeps those."""
        self.args = {**self.args, **args}

    def __exit__(self, *exc) -> bool:
        tr = self.tracer
        if tr is not None:
            tr._append(tr.complete_event(self.name, self._t0, tr.now_us(),
                                         **self.args))
        if self._ann is not None:
            self._ann.__exit__(*exc)
        return False


def span(name: str, **args: Any) -> _Span:
    """``with span("serve/encode", bucket=8): ...`` — the program's one
    span function (see the module docstring)."""
    return _Span(name, args, _current)


def instant(name: str, **args: Any) -> None:
    """Point-in-time marker in the installed tracer; the profiler's
    trace has no instants, so without a tracer this is a no-op."""
    tr = _current
    if tr is not None:
        shared = getattr(_local, "tags", None)
        tr.instant(name, **({**shared, **args} if shared else args))


def validate_chrome_trace(obj: Any) -> Optional[str]:
    """Schema check for the trace JSON this module writes — returns an
    error string or None.  The contract Perfetto's JSON importer needs:
    a ``traceEvents`` list whose entries carry ``name``/``ph``/``ts``
    (+ ``dur`` for "X" events), with numeric timestamps."""
    if not isinstance(obj, dict):
        return "trace must be a JSON object"
    events = obj.get("traceEvents")
    if not isinstance(events, list):
        return "missing traceEvents list"
    for i, ev in enumerate(events):
        if not isinstance(ev, dict):
            return f"event {i} is not an object"
        for key in ("name", "ph", "ts"):
            if key not in ev:
                return f"event {i} missing {key!r}"
        if not isinstance(ev["ts"], (int, float)):
            return f"event {i} ts is not numeric"
        if ev["ph"] == "X" and not isinstance(ev.get("dur"), (int, float)):
            return f"event {i} is 'X' but has no numeric dur"
    return None
