"""The program's host spans, on the profiler's clock and in a log.

``span(name, **attrs)`` is a context manager that opens a
``jax.profiler.TraceAnnotation`` carrying the monotonic clock at its start
(``mono_ns``), so a profiler trace holds the span on its host plane and a
reader can put it on the device trace's clock.  While recording is on it
also appends one ``Record`` to a bounded in-memory log when it closes:
id, parent id (the innermost open span of the thread), name, thread,
start and end in monotonic ns, and attrs.  ``record`` appends an interval
whose ends were measured elsewhere.

Recording is on while a profiler trace runs
(``TraceAnnotation.is_enabled()``) and inside ``with recording():``.  Off,
a span costs that check and the annotation's own, and logs nothing.
Spans are host code: none belongs inside a jitted function.

A span may stay open across a generator's ``yield`` only inside
``Span.suspended``, which takes it (and the spans opened inside it) off the
thread's stack while the consumer runs, so what the consumer opens in
the meantime is not parented to the generator's spans.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import itertools
import threading
import time
from typing import Any

import jax

LOG_LIMIT = 1 << 16  # records kept; the oldest go first

_log: collections.deque = collections.deque(maxlen=LOG_LIMIT)
_ids = itertools.count(1)
_local = threading.local()
_forced = [0]  # depth of open ``recording()`` blocks, over all threads
_forced_lock = threading.Lock()


@dataclasses.dataclass(frozen=True)
class Record:
    id: int
    parent: int | None
    name: str
    thread: str
    t0: int  # monotonic ns
    t1: int
    attrs: dict[str, Any]


def enabled() -> bool:
    return _forced[0] > 0 or jax.profiler.TraceAnnotation.is_enabled()


@contextlib.contextmanager
def recording():
    """Log spans while the body runs, profiler or not."""
    with _forced_lock:
        _forced[0] += 1
    try:
        yield
    finally:
        with _forced_lock:
            _forced[0] -= 1


def spans() -> list[Record]:
    return list(_log)


def clear() -> None:
    _log.clear()


def _stack() -> list:
    st = getattr(_local, "stack", None)
    if st is None:
        st = _local.stack = []
    return st


def current() -> "Span | None":
    """The innermost recorded span open on this thread."""
    st = _stack()
    return st[-1] if st else None


class Span:
    def __init__(self, name: str, attrs: dict):
        self.name, self.attrs = name, attrs
        self.parent: int | None = None
        self.id = 0  # nonzero while recorded
        self.t0 = 0

    @property
    def recording(self) -> bool:
        return self.id != 0

    def __enter__(self) -> "Span":
        self.t0 = time.monotonic_ns()
        self._ann = jax.profiler.TraceAnnotation(self.name, mono_ns=self.t0)
        self._ann.__enter__()
        if enabled():
            self.id = next(_ids)
            st = _stack()
            if st:
                self.parent = st[-1].id
            st.append(self)
        return self

    def __exit__(self, *exc) -> None:
        self._ann.__exit__(*exc)
        if self.id:
            _pop(self)
            _log.append(Record(self.id, self.parent, self.name, threading.current_thread().name,
                               self.t0, time.monotonic_ns(), self.attrs))

    def drop(self) -> None:
        """Log nothing for this span (it turned out to hold none of the
        work it names) and take it off the stack now, so what opens after
        it parents to the span outside."""
        if self.id:
            _pop(self)
            self.id = 0

    @contextlib.contextmanager
    def suspended(self, name: str):
        """Around a ``yield`` inside this span: off this thread's stack,
        with the spans opened inside it, while the consumer runs; the time
        logged as a child ``name``; back on the stack of whichever thread
        resumes."""
        if not self.id:
            yield
            return
        st = _stack()
        i = next(k for k in range(len(st) - 1, -1, -1) if st[k] is self)
        saved = st[i:]
        del st[i:]
        t0 = time.monotonic_ns()
        try:
            yield
        finally:
            record(name, t0, time.monotonic_ns(), parent=self)
            _stack().extend(saved)


def _pop(s: Span) -> None:
    st = _stack()
    for k in range(len(st) - 1, -1, -1):
        if st[k] is s:
            del st[k:]
            return


def span(name: str, **attrs) -> Span:
    """A host span named ``name``, under the innermost span open on this
    thread."""
    return Span(name, attrs)


@contextlib.contextmanager
def under(parent: Span | None):
    """Parent this thread's spans to ``parent`` (opened on another thread)
    while the body runs."""
    if parent is None or not parent.id:
        yield
        return
    st = _stack()
    st.append(parent)
    try:
        yield
    finally:
        _pop(parent)


def record(name: str, t0: int, t1: int, parent: Span | None = None, **attrs) -> None:
    """Log an interval measured elsewhere (monotonic ns), if recording."""
    if not enabled():
        return
    if parent is None:
        parent = current()
    _log.append(Record(next(_ids), parent.id if parent is not None else None, name,
                       threading.current_thread().name, int(t0), int(t1), attrs))
