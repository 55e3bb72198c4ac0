"""Spans and counters of the request path, recorded in memory on request.

Recording is off by default.  Then :func:`span` returns one shared null
context and :func:`count` does nothing; callers that would compute a
count first check :func:`on`.  Inside ``with recording() as rec:``
every span keeps ``(name, start_ns, end_ns, parent, request)`` in
``rec.spans`` and opens a ``jax.profiler.TraceAnnotation`` of the same
name, so the interval also lands in a profiler trace; counters add up
in ``rec.counters``, and per request id in ``rec.request_counters``.
A span opened while none is open is a root and starts a new request
id::

    from repro.core import spans
    with spans.recording() as rec:
        results = run_many(specs)
    rec.self_ns()["streamsim.wait"]    # ns waiting on the device

Stamps come from ``time.time_ns()``, the clock of the profiler's host
events, so a span and its annotation differ by one offset: the trace's
own time base.  Nothing in the simulator reads a span or a counter, so
recording cannot change a result.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import time
from typing import Any, Callable, Iterator, NamedTuple, Optional


class Span(NamedTuple):
    name: str
    start_ns: int
    end_ns: int
    parent: int      # index in ``Record.spans`` of the enclosing span, -1
    request: int     # id of the root span this one runs under


@dataclasses.dataclass
class Record:
    """What one :func:`recording` saw, spans in the order they opened."""

    spans: list = dataclasses.field(default_factory=list)
    counters: dict = dataclasses.field(default_factory=dict)
    #: {request id: {counter: total}} of the counts made under a span
    request_counters: dict = dataclasses.field(default_factory=dict)
    n_requests: int = 0
    _open: list = dataclasses.field(default_factory=list, repr=False)

    def requests(self) -> list:
        """Per request id, nanoseconds per span name less the part its
        children cover (self time)."""
        out: list = [{} for _ in range(self.n_requests)]
        for s in self.spans:
            own = out[s.request]
            d = s.end_ns - s.start_ns
            own[s.name] = own.get(s.name, 0) + d
            if s.parent >= 0:
                p = self.spans[s.parent].name
                own[p] = own.get(p, 0) - d
        return out

    def self_ns(self) -> dict:
        """Self time per span name (ns) over every request."""
        out: dict = {}
        for own in self.requests():
            for name, d in own.items():
                out[name] = out.get(name, 0) + d
        return out


_REC: Optional[Record] = None
_NULL = contextlib.nullcontext()


class _Span:
    __slots__ = ("rec", "name", "index", "ann")

    def __init__(self, rec: Record, name: str) -> None:
        self.rec, self.name = rec, name

    def __enter__(self) -> "_Span":
        rec = self.rec
        self.ann = _annotation(self.name)
        self.ann.__enter__()
        parent = rec._open[-1] if rec._open else -1
        if parent < 0:
            rec.n_requests += 1
        request = (rec.spans[parent].request if parent >= 0
                   else rec.n_requests - 1)
        self.index = len(rec.spans)
        rec._open.append(self.index)
        # streamlint: disable=SL403 -- span stamps are telemetry, never read by results
        start = time.time_ns()
        rec.spans.append(Span(self.name, start, -1, parent, request))
        return self

    def __exit__(self, *exc: Any) -> None:
        # streamlint: disable=SL403 -- span stamps are telemetry, never read by results
        end = time.time_ns()
        rec = self.rec
        rec._open.pop()
        rec.spans[self.index] = rec.spans[self.index]._replace(end_ns=end)
        self.ann.__exit__(*exc)


def _annotation(name: str) -> Any:
    from jax.profiler import TraceAnnotation
    return TraceAnnotation(name)


def on() -> bool:
    return _REC is not None


def span(name: str) -> Any:
    """A context manager timing ``name``; the shared null context when
    recording is off."""
    if _REC is None:
        return _NULL
    return _Span(_REC, name)


def traced(name: str) -> Callable:
    """Decorator: each call of the function runs inside ``span(name)``."""
    def wrap(fn: Callable) -> Callable:
        @functools.wraps(fn)
        def inner(*args: Any, **kw: Any) -> Any:
            with span(name):
                return fn(*args, **kw)
        return inner
    return wrap


def count(name: str, n: int) -> None:
    rec = _REC
    if rec is not None:
        rec.counters[name] = rec.counters.get(name, 0) + n
        if rec._open:
            own = rec.request_counters.setdefault(
                rec.spans[rec._open[0]].request, {})
            own[name] = own.get(name, 0) + n


@contextlib.contextmanager
def recording() -> Iterator[Record]:
    """Record spans and counters until the block ends; yields the
    :class:`Record`."""
    global _REC
    if _REC is not None:
        raise RuntimeError("spans are already being recorded")
    _REC = Record()
    try:
        yield _REC
    finally:
        _REC = None
