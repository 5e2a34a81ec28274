"""Span tracer: timed spans on the profiler's clock, with nesting and a ring.

``span("serve.plan", attrs={...}, histogram=h)`` is a context manager
around one *synchronous* section of code:

* Once JAX is imported, it also opens a profiler ``TraceMe``
  (``jax.profiler.TraceAnnotation``) named by the bare span name, with
  ``attrs`` as its metadata.  In a ``jax.profiler`` trace every span is
  then a host event on the clock of the device events, so a device idle
  gap can be attributed to the innermost span over it.  This module never
  imports JAX itself: a process without JAX (a load generator, the numpy
  backend) records the ring alone.
* It stamps its start on the profiler's host clock (``time.time_ns``, the
  wall clock TSL stamps ``TraceMe`` events with) and times its duration on
  ``time.perf_counter_ns``; parent/child nesting comes from a thread-local
  stack (each thread has its own, so pipeline worker threads nest
  correctly and independently), and the finished span goes into a bounded
  ring buffer -- old spans fall off, the tracer never grows without bound.
* With ``histogram``, a clean exit observes the duration in seconds
  there: the span and its metric are one measurement.  An exception
  propagating out of the body marks the span ``error``, observes nothing
  and re-raises.

A span must not stay open across an ``await``: the thread-local stack and
the ``TraceMe`` would then cover other coroutines' work.  Waits are
measured as intervals into histograms instead.

Two record kinds share the ring: spans (a duration, a parent, an
ok/error status) and events -- zero-duration structured facts
(``event()``), e.g. the adaptive selector's mode-switch
:class:`~repro.core.select.SelectionEvent`.
"""
from __future__ import annotations

import itertools
import sys
import threading
import time
from collections import deque
from typing import Dict, List, Optional

__all__ = ["Span", "SpanTracer", "tracer", "span", "event"]


class Span:
    """A finished record.  ``start_s`` is on the profiler's host clock
    (Unix epoch seconds, ``time.time_ns() / 1e9``)."""

    __slots__ = ("name", "attrs", "span_id", "parent_id", "thread",
                 "start_s", "duration_s", "status", "kind")

    def __init__(self, name: str, attrs: Optional[Dict], span_id: int,
                 parent_id: Optional[int], thread: str, start_s: float,
                 duration_s: float, status: str, kind: str) -> None:
        self.name = name
        self.attrs = attrs or {}
        self.span_id = span_id
        self.parent_id = parent_id
        self.thread = thread
        self.start_s = start_s
        self.duration_s = duration_s
        self.status = status
        self.kind = kind

    def as_dict(self) -> dict:
        return {"name": self.name, "attrs": dict(self.attrs),
                "span_id": self.span_id, "parent_id": self.parent_id,
                "thread": self.thread, "start_s": self.start_s,
                "duration_s": self.duration_s, "status": self.status,
                "kind": self.kind}

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return (f"Span({self.name!r}, id={self.span_id}, "
                f"parent={self.parent_id}, {self.duration_s * 1e6:.1f}us, "
                f"{self.status})")


def _trace_me(name: str, attrs: Optional[Dict]):
    """An entered profiler ``TraceMe`` for ``name`` while a profiler trace
    is being collected, else ``None``.  Looks JAX up in ``sys.modules``
    only (``jax.profiler`` is absent until JAX is imported, and has no
    ``TraceAnnotation`` while JAX is half-way through its import)."""
    ann = getattr(sys.modules.get("jax.profiler"), "TraceAnnotation", None)
    if ann is None or not ann.is_enabled():
        return None
    tm = ann(name, **attrs) if attrs else ann(name)
    tm.__enter__()
    return tm


class _SpanScope:
    """The context manager ``SpanTracer.span`` returns."""

    __slots__ = ("_tracer", "_name", "_attrs", "_histogram", "_id",
                 "_parent", "_tm", "_start_ns", "_t0")

    def __init__(self, tracer: "SpanTracer", name: str,
                 attrs: Optional[Dict], histogram) -> None:
        self._tracer = tracer
        self._name = name
        self._attrs = attrs
        self._histogram = histogram
        self._id = None

    def __enter__(self) -> Optional[int]:
        trc = self._tracer
        if trc.enabled:
            stack = trc._stack()
            self._parent = stack[-1] if stack else None
            self._id = next(trc._ids)
            stack.append(self._id)
            self._tm = _trace_me(self._name, self._attrs)
        self._start_ns = time.time_ns()
        self._t0 = time.perf_counter_ns()
        return self._id

    def __exit__(self, exc_type, exc, tb) -> bool:
        dur = (time.perf_counter_ns() - self._t0) / 1e9
        if exc_type is None and self._histogram is not None:
            self._histogram.observe(dur)
        if self._id is not None:
            if self._tm is not None:
                self._tm.__exit__(exc_type, exc, tb)
            self._tracer._stack().pop()
            self._tracer._finish(Span(
                self._name, self._attrs, self._id, self._parent,
                threading.current_thread().name, self._start_ns / 1e9, dur,
                "ok" if exc_type is None else "error", "span"))
        return False


class SpanTracer:
    """Bounded-retention tracer; see the module docstring."""

    def __init__(self, capacity: int = 2048, enabled: bool = True) -> None:
        if capacity < 1:
            raise ValueError("capacity must be >= 1")
        self.capacity = capacity
        self.enabled = enabled
        self._ring: deque = deque(maxlen=capacity)
        self._lock = threading.Lock()
        self._ids = itertools.count(1)
        self._tls = threading.local()

    # ------------------------------------------------------------- recording
    def _stack(self) -> list:
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []
        return st

    def _finish(self, rec: Span) -> None:
        with self._lock:
            self._ring.append(rec)

    def span(self, name: str, attrs: Optional[Dict] = None,
             histogram=None) -> _SpanScope:
        """Context manager timing a synchronous section; yields the span
        id (``None`` when the tracer is disabled, which still observes
        ``histogram``)."""
        return _SpanScope(self, name, attrs, histogram)

    def event(self, name: str, attrs: Optional[Dict] = None) -> None:
        """Zero-duration structured record, nested under the current span
        of the calling thread (if any)."""
        if not self.enabled:
            return
        stack = self._stack()
        self._finish(Span(name, attrs, next(self._ids),
                          stack[-1] if stack else None,
                          threading.current_thread().name,
                          time.time_ns() / 1e9, 0.0, "ok", "event"))

    # ------------------------------------------------------------- consumers
    def records(self, name: Optional[str] = None,
                kind: Optional[str] = None) -> List[Span]:
        """Finished records, oldest first, optionally filtered."""
        with self._lock:
            recs = list(self._ring)
        if name is not None:
            recs = [r for r in recs if r.name == name]
        if kind is not None:
            recs = [r for r in recs if r.kind == kind]
        return recs

    def snapshot(self) -> List[dict]:
        return [r.as_dict() for r in self.records()]

    def reset(self) -> None:
        with self._lock:
            self._ring.clear()


# Process-default tracer, sibling of the default metrics registry.
_DEFAULT = SpanTracer()


def tracer() -> SpanTracer:
    return _DEFAULT


def span(name: str, attrs: Optional[Dict] = None, histogram=None):
    """``with obs.span("serve.plan", attrs={"seq": 3}, histogram=h): ...``
    against the default tracer."""
    return _DEFAULT.span(name, attrs, histogram)


def event(name: str, attrs: Optional[Dict] = None) -> None:
    _DEFAULT.event(name, attrs)
