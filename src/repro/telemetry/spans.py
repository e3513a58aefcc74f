"""Spans inside the program: where each layer's time goes, per thread.

``span(name, **ids)`` is a context manager around one call of a layer (a
stage enforcement, a decode step, a checkpoint write). Off, the default, it
returns one shared no-op object; the cost is one check of a module flag, with
no clock read and no allocation. On (:func:`enable`), each span is kept in a
bounded in-memory buffer as a :class:`SpanRecord` on ``time.perf_counter_ns``
when it closes. Its parent is the span open on the same thread when it opened,
and ``ids`` carries identifiers such as a request's. Nothing is written out:
a reader asks for :func:`records` and may :func:`clear` them.

While on, in a process that has already imported ``jax``, each span also opens
a ``jax.profiler.TraceAnnotation`` of the same name, with ``span_id`` as its
metadata, so that a profiler trace holds the span on the host clock that the
device timeline is mapped onto. This module never imports ``jax`` itself: the
data plane (``repro.core``) stays importable without it.

Every span name the program uses starts with ``paio.``.
"""
from __future__ import annotations

import itertools
import sys
import threading
import time
from collections import deque
from typing import Any, Deque, Dict, Iterable, List, NamedTuple, Optional

#: records kept by default; the oldest go first once the buffer is full
DEFAULT_CAPACITY = 1 << 20


class SpanRecord(NamedTuple):
    span_id: int
    #: the span open on the same thread when this one opened; None at the top
    parent_id: Optional[int]
    name: str
    #: name of the thread the span ran on
    thread: str
    t0_ns: int
    t1_ns: int
    ids: Dict[str, Any]

    @property
    def duration_ns(self) -> int:
        return self.t1_ns - self.t0_ns


class _Off:
    """The shared span returned while recording is off."""

    __slots__ = ()

    def __enter__(self) -> "_Off":
        return self

    def __exit__(self, exc_type: Any, exc: Any, tb: Any) -> None:
        return None


_OFF = _Off()


class _Recorder:
    """The buffer, the id source and each thread's stack of open spans."""

    def __init__(self, capacity: int) -> None:
        self.buffer: Deque[SpanRecord] = deque(maxlen=capacity)
        self.ids = itertools.count(1)
        self.local = threading.local()
        #: records that fell out of the full buffer
        self.dropped = 0
        self.lock = threading.Lock()

    def stack(self) -> List[int]:
        try:
            return self.local.stack
        except AttributeError:
            self.local.stack = []
            return self.local.stack

    def keep(self, record: SpanRecord) -> None:
        with self.lock:
            if len(self.buffer) == self.buffer.maxlen:
                self.dropped += 1
            self.buffer.append(record)


class _Span:
    __slots__ = ("_rec", "_name", "_ids", "_id", "_parent", "_t0", "_ann")

    def __init__(self, rec: _Recorder, name: str, ids: Dict[str, Any]) -> None:
        self._rec = rec
        self._name = name
        self._ids = ids
        self._ann = None

    def __enter__(self) -> "_Span":
        rec = self._rec
        stack = rec.stack()
        self._id = next(rec.ids)
        self._parent = stack[-1] if stack else None
        stack.append(self._id)
        jax = sys.modules.get("jax")
        profiler = getattr(jax, "profiler", None)
        if profiler is not None:
            self._ann = profiler.TraceAnnotation(self._name, span_id=self._id)
            self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type: Any, exc: Any, tb: Any) -> None:
        t1 = time.perf_counter_ns()
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        stack = self._rec.stack()
        if stack and stack[-1] == self._id:
            stack.pop()
        self._rec.keep(
            SpanRecord(
                self._id, self._parent, self._name, threading.current_thread().name,
                self._t0, t1, self._ids,
            )
        )


#: the recorder while on; None while off (the flag ``span`` checks)
_recorder: Optional[_Recorder] = None


def span(name: str, **ids: Any):
    """A span named ``name`` around a ``with`` block; ``ids`` identify what
    it served (a request, a leaf). A no-op unless :func:`enable` was called."""
    if _recorder is None:
        return _OFF
    return _Span(_recorder, name, ids)


def enable(capacity: int = DEFAULT_CAPACITY) -> None:
    """Start recording, into a new buffer of ``capacity`` records."""
    global _recorder
    _recorder = _Recorder(capacity)


def disable() -> None:
    """Stop recording and drop the buffer."""
    global _recorder
    _recorder = None


def enabled() -> bool:
    return _recorder is not None


def records() -> List[SpanRecord]:
    """The spans closed so far, in the order they closed (children before
    their parents); empty while off."""
    rec = _recorder
    if rec is None:
        return []
    with rec.lock:
        return list(rec.buffer)


def dropped() -> int:
    """How many records the full buffer let go, oldest first."""
    rec = _recorder
    return rec.dropped if rec is not None else 0


def clear() -> None:
    """Empty the buffer; recording goes on."""
    rec = _recorder
    if rec is not None:
        with rec.lock:
            rec.buffer.clear()
            rec.dropped = 0


def self_ns(spans: Iterable[SpanRecord]) -> Dict[int, int]:
    """Each span's self time: its duration less its children's, by span id.
    Children run on their parent's thread and inside it, so they never
    overlap one another."""
    spans = list(spans)
    out = {s.span_id: s.duration_ns for s in spans}
    for s in spans:
        if s.parent_id in out:
            out[s.parent_id] -= s.duration_ns
    return out
