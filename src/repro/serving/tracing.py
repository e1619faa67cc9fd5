"""In-program spans and counters of the serving sessions.

A `Tracer` keeps, per span name, the number of spans, their total wall
time and their self time (the total minus the part covered by child
spans), and per counter name a running count. Only those totals live in
memory. Every span is also a `jax.profiler.TraceAnnotation`, so while a
profiler trace is active it lands in the trace on the same clock as the
device ops; with no trace active the annotation costs well under a
microsecond. It is always on: each session owns a tracer and its
``result()`` carries ``snapshot()`` as the report's ``telemetry``.

Span names start with ``splitee.``. Keyword ids (``push``, ``step``) tie
a span to its request in the profiler trace; they are formatted only
while a trace is active.
"""
from __future__ import annotations

import threading
import time
from typing import Any, Dict, List

import jax

_Annotation = jax.profiler.TraceAnnotation


class _Span:
    """One open span: times itself and charges its duration to its
    parent's children (context manager from `Tracer.span`)."""

    __slots__ = ("_tracer", "_name", "_ids", "_ann", "_t0", "child_ns")

    def __init__(self, tracer: "Tracer", name: str, ids: Dict[str, Any]):
        self._tracer = tracer
        self._name = name
        self._ids = ids

    def __enter__(self) -> "_Span":
        if self._ids and _Annotation.is_enabled():
            self._ann = _Annotation(self._name, **self._ids)
        else:
            self._ann = _Annotation(self._name)
        self._ann.__enter__()
        self._tracer._stack().append(self)
        self.child_ns = 0
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> bool:
        dur = time.perf_counter_ns() - self._t0
        stack = self._tracer._stack()
        stack.pop()
        if stack:
            stack[-1].child_ns += dur
        self._tracer._record(self._name, dur, dur - self.child_ns)
        self._ann.__exit__(*exc)
        return False


class Tracer:
    """Per-name span totals and counters of one serving session.

    ``span(name, **ids)`` is a context manager; ``count(name, k)`` adds
    to a counter; ``add(name, ns)`` adds a duration that is not on the
    call stack (a request's queue wait) as one span with no children.
    The stack of open spans is kept per thread; the totals are shared
    and updated under a lock.
    """

    def __init__(self):
        self._local = threading.local()
        self._lock = threading.Lock()
        self._spans: Dict[str, List[int]] = {}   # name -> [n, total, self]
        self._counts: Dict[str, int] = {}

    def _stack(self) -> List[_Span]:
        try:
            return self._local.stack
        except AttributeError:
            self._local.stack = []
            return self._local.stack

    def _record(self, name: str, total_ns: int, self_ns: int) -> None:
        with self._lock:
            rec = self._spans.get(name)
            if rec is None:
                self._spans[name] = [1, total_ns, self_ns]
            else:
                rec[0] += 1
                rec[1] += total_ns
                rec[2] += self_ns

    def span(self, name: str, **ids) -> _Span:
        return _Span(self, name, ids)

    def count(self, name: str, k: int = 1) -> None:
        with self._lock:
            self._counts[name] = self._counts.get(name, 0) + int(k)

    def add(self, name: str, ns: int) -> None:
        self._record(name, int(ns), int(ns))

    def snapshot(self) -> Dict[str, Dict[str, Any]]:
        """``{"spans": {name: {"n", "total_ms", "self_ms"}},
        "counts": {name: int}}``, a copy."""
        with self._lock:
            return {
                "spans": {name: {"n": n, "total_ms": tot / 1e6,
                                 "self_ms": own / 1e6}
                          for name, (n, tot, own) in self._spans.items()},
                "counts": dict(self._counts),
            }
