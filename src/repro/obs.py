"""Program spans and counters, recorded only while the JAX profiler collects.

The profiler is the switch: run ``jax.profiler.trace(directory)`` (or
``start_trace`` / ``stop_trace``) around a stretch of work, read the
timeline in the xplane it writes, and read the per-name totals of that
stretch from :func:`totals`.  While the profiler is off, :func:`span`
returns a shared no-op and :func:`count` does nothing, so the program
pays one ``TraceMe.is_enabled`` check per call site.

While it collects, ``span(name, **ids)`` opens a
``jax.profiler.TraceAnnotation(name, **ids)`` (a
``StepTraceAnnotation`` when ``ids`` holds ``step_num``), so the span is
a host event on the device trace's clock, and on exit appends a
:class:`Record` to an in-memory list.  Its parent is the innermost
program span open on the same thread.  Whether a span records is
decided when it is entered.
"""

from __future__ import annotations

import threading
import time
from typing import NamedTuple

from jax.profiler import StepTraceAnnotation, TraceAnnotation

_enabled = TraceAnnotation.is_enabled


class Record(NamedTuple):
    """One closed span; ``child_ns`` is the time its direct children
    covered."""

    name: str
    parent: str | None
    start_ns: int
    end_ns: int
    ids: dict
    child_ns: int


# Closed spans as plain tuples in Record's field order: appending a tuple
# is atomic and cheap, so recording takes no lock.
_records: list[tuple] = []
_counters: dict[str, int] = {}
_lock = threading.Lock()
_local = threading.local()
_now = time.perf_counter_ns


def _stack() -> list:
    try:
        return _local.stack
    except AttributeError:
        _local.stack = []
        return _local.stack


class _Noop:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> bool:
        return False


_NOOP = _Noop()


class _Span:
    __slots__ = ("name", "ids", "parent", "annotation", "start", "child_ns")

    def __init__(self, name: str, ids: dict):
        self.name, self.ids, self.child_ns = name, ids, 0

    def __enter__(self):
        stack = _stack()
        self.parent = stack[-1] if stack else None
        kind = StepTraceAnnotation if "step_num" in self.ids else TraceAnnotation
        # The host event starts when the annotation is built and ends in
        # its __exit__: the record's clock reads sit next to both.
        self.annotation = kind(self.name, **self.ids)
        self.start = _now()
        self.annotation.__enter__()
        stack.append(self)
        return self

    def __exit__(self, *exc) -> bool:
        annotation = self.annotation
        if annotation is None:  # closed early by end()
            return False
        annotation.__exit__(None, None, None)
        end = _now()
        self.annotation = None
        _local.stack.pop()  # spans nest: this one is the innermost open
        parent = self.parent
        if parent is not None:
            parent.child_ns += end - self.start
            parent = parent.name
        _records.append((self.name, parent, self.start, end, self.ids,
                         self.child_ns))
        return False


def span(name: str, **ids):
    """A context manager timing ``name``; a shared no-op unless the
    profiler is collecting."""
    if not _enabled():
        return _NOOP
    return _Span(name, ids)


def end(name: str) -> None:
    """Close the innermost open program span if it is named ``name``
    (a phase that ends inside a callee, as ``solve.prepare`` does when
    the outer loop starts); its ``with`` block's exit is then a no-op."""
    stack = _stack()
    if stack and stack[-1].name == name:
        stack[-1].__exit__(None, None, None)


def count(name: str, n: int) -> None:
    """Add ``n`` to the counter ``name`` while the profiler collects."""
    if not _enabled():
        return
    with _lock:
        _counters[name] = _counters.get(name, 0) + n


def records() -> list[Record]:
    """The spans closed since the last :func:`reset`, in closing order."""
    return [Record(*r) for r in list(_records)]


def totals() -> dict:
    """``{"spans": {name: {"count", "seconds", "self_seconds"}},
    "counters": {name: n}}`` over what was recorded since the last
    :func:`reset`; self time is a span's time minus what its direct
    children covered."""
    spans: dict[str, dict] = {}
    for name, _, start, end, _, child_ns in list(_records):
        t = spans.setdefault(name, {"count": 0, "seconds": 0.0,
                                    "self_seconds": 0.0})
        t["count"] += 1
        t["seconds"] += (end - start) * 1e-9
        t["self_seconds"] += (end - start - child_ns) * 1e-9
    with _lock:
        return {"spans": spans, "counters": dict(_counters)}


def reset() -> None:
    """Forget every recorded span and counter."""
    _records.clear()
    with _lock:
        _counters.clear()
