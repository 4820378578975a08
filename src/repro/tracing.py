"""Host spans and counters of the plan path.

``span(name)`` marks one layer of a plan request. Off, which is the default,
it costs one global check and returns a shared no-op context: nothing is
recorded and no profiler annotation is opened. Inside ``recording()`` every
span is kept in memory (name, ids, start and end on ``time.perf_counter_ns``)
and also opens a ``jax.profiler.TraceAnnotation``, so that a running
profiler shows the span on its host plane, on the clock of the device
planes. An operator's trace is the profiler's; there is no exporter.

A span opened while no span is open in its context starts a new request;
the spans opened inside it carry its request id and name their parent.

``count(name, n)`` adds to a process-wide counter, always on;
``counters()`` reads them.
"""

from __future__ import annotations

import contextlib
import itertools
import time
from collections import defaultdict
from contextvars import ContextVar
from dataclasses import dataclass, field

_NOOP = contextlib.nullcontext()
_recorder: Recorder | None = None
_open: ContextVar[Span | None] = ContextVar("pccl_open_span", default=None)
_counters: defaultdict[str, int] = defaultdict(int)


@dataclass
class Span:
    name: str
    span_id: int
    parent_id: int | None
    request_id: int
    start_ns: int
    end_ns: int | None = None

    @property
    def duration_ns(self) -> int:
        return self.end_ns - self.start_ns


@dataclass
class Recorder:
    """The spans closed while ``recording()`` was entered, in closing order."""

    spans: list[Span] = field(default_factory=list)
    _ids: itertools.count = field(default_factory=lambda: itertools.count(1))
    _requests: itertools.count = field(
        default_factory=lambda: itertools.count(1))

    def total_ns(self, name: str) -> int:
        """Summed duration of the spans called ``name``."""
        return sum(s.duration_ns for s in self.spans if s.name == name)

    def self_ns(self, name: str) -> int:
        """Summed self time of the spans called ``name``: each one's
        duration less the durations of its direct children."""
        ids = {s.span_id for s in self.spans if s.name == name}
        inside = sum(s.duration_ns for s in self.spans if s.parent_id in ids)
        return self.total_ns(name) - inside


class _Recording:
    """The context manager of one span while a recorder is on."""

    __slots__ = ("_rec", "_name", "_span", "_token", "_annotation")

    def __init__(self, rec: Recorder, name: str):
        self._rec, self._name = rec, name

    def __enter__(self) -> Span:
        from jax.profiler import TraceAnnotation

        parent = _open.get()
        self._span = Span(
            self._name, next(self._rec._ids),
            parent.span_id if parent else None,
            parent.request_id if parent else next(self._rec._requests),
            time.perf_counter_ns())
        self._token = _open.set(self._span)
        self._annotation = TraceAnnotation(self._name)
        self._annotation.__enter__()
        return self._span

    def __exit__(self, *exc) -> bool:
        self._annotation.__exit__(*exc)
        self._span.end_ns = time.perf_counter_ns()
        _open.reset(self._token)
        self._rec.spans.append(self._span)
        return False


def span(name: str):
    """A context manager that marks the layer ``name`` (``pccl.*``)."""
    rec = _recorder
    if rec is None:
        return _NOOP
    return _Recording(rec, name)


@contextlib.contextmanager
def recording():
    """Record every span opened while entered; yields the ``Recorder``."""
    global _recorder
    saved, rec = _recorder, Recorder()
    _recorder = rec
    try:
        yield rec
    finally:
        _recorder = saved


def count(name: str, n: int = 1) -> None:
    _counters[name] += n


def counters() -> dict[str, int]:
    """A copy of every counter, by name."""
    return dict(_counters)
