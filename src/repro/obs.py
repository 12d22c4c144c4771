"""Host spans on the profiler's clock and in a bounded in-memory log.

One mechanism for the whole program::

    with obs.span("repro.engine.stage", batch=7) as attrs:
        ...
        attrs["cols"] = 128        # attrs known only later go to the log

``span`` does two things.  It opens a ``jax.profiler.TraceAnnotation``,
which costs about a microsecond when no profile is being taken and, when
one is, puts the span on the same timeline as the device operations.  And
on exit it appends one :class:`Span` record, timed on :data:`clock`
(``time.perf_counter``), to a bounded log that :func:`spans` returns.  A
span's parent is the innermost span open on the same thread.

A span times the host only.  Around an asynchronous call (a kernel launch,
a ``device_put``) it measures the enqueue, not the device work; nothing
here synchronises with the device.

Every backend compile reported by ``jax.monitoring`` is logged as a
``repro.compile`` span that ends when the event is reported and lasts the
reported duration, its parent the span open on the compiling thread, so a
recompile names the step that caused it.

Every span name starts with ``repro.``.
"""
from __future__ import annotations

import collections
import contextlib
import itertools
import threading
import time
from typing import Dict, List, NamedTuple, Optional

import jax

#: Records the log holds; older ones are dropped, and counted.
MAX_SPANS: int = 1 << 14

#: The clock spans are timed on (seconds).
clock = time.perf_counter

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Span(NamedTuple):
    """One closed span."""

    id: int
    parent: Optional[int]        # id of the enclosing span on this thread
    name: str
    start: float                 # seconds on ``clock``
    end: float
    thread: str
    attrs: Dict[str, object]


_log: "collections.deque[Span]" = collections.deque(maxlen=MAX_SPANS)
_lock = threading.Lock()
_dropped = 0
_ids = itertools.count()
_local = threading.local()


def _stack() -> List[int]:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


def _append(rec: Span) -> None:
    global _dropped
    with _lock:
        if len(_log) == _log.maxlen:
            _dropped += 1
        _log.append(rec)


@contextlib.contextmanager
def span(name: str, **attrs):
    """Time the block as span ``name``; yields its mutable ``attrs``."""
    stack = _stack()
    sid = next(_ids)
    parent = stack[-1] if stack else None
    stack.append(sid)
    start = clock()
    try:
        with jax.profiler.TraceAnnotation(name, **attrs):
            yield attrs
    finally:
        end = clock()
        stack.pop()
        _append(Span(sid, parent, name, start, end,
                     threading.current_thread().name, attrs))


def spans() -> List[Span]:
    """The logged spans, in the order they closed."""
    with _lock:
        return list(_log)


def dropped() -> int:
    """How many records the bounded log has dropped."""
    return _dropped


def reset() -> None:
    """Empty the log and zero the drop count."""
    global _dropped
    with _lock:
        _log.clear()
        _dropped = 0


def _on_duration(event: str, duration: float, **_) -> None:
    if event != COMPILE_EVENT:
        return
    stack = _stack()
    end = clock()
    _append(Span(next(_ids), stack[-1] if stack else None, "repro.compile",
                 end - duration, end, threading.current_thread().name, {}))


jax.monitoring.register_event_duration_secs_listener(_on_duration)
