"""Spans of the program's own steps: kept in memory, and written into any
running profiler trace.

    from repro import obs

    with obs.span("session.host.dp", pairs=16, useful=11):
        ...

A span does three things:

* it enters a ``jax.profiler.TraceAnnotation(name)``, so a running
  profiler trace (``jax.profiler.trace(dir)``) holds the span on its host
  plane, on the same clock as the device's operations; with no profiler
  running that costs about half a microsecond;
* it appends a :class:`Span` to a bounded ring in memory when it closes,
  with ``t0`` and ``t1`` from ``time.perf_counter()``, the id of the span
  it was opened under on the same thread, and its attributes (counts of
  the step it times, such as ``pairs=16``);
* it carries the request id of the context that opened it: inside
  ``with obs.request():`` every span of that thread shares one id (the
  engine opens one per batch).

Recording is always on.  Appends are safe across threads, and each thread
keeps its own parent.  The ring keeps the newest :data:`CAPACITY` spans;
:func:`spans_between` raises :class:`RingWrapped` when spans of the
interval it is asked for were overwritten, so a reader never sums a
window that is missing part of its spans.

Readers: :func:`spans_between` (the spans inside an interval of the
``perf_counter`` clock) and :func:`self_times` (each span's duration less
the part of it its children cover).
"""

from __future__ import annotations

import contextvars
import functools
import itertools
import threading
import time
from collections import deque
from typing import Iterable, NamedTuple

from jax.profiler import TraceAnnotation

__all__ = [
    "CAPACITY",
    "Ring",
    "RingWrapped",
    "Span",
    "request",
    "self_times",
    "span",
    "spanned",
    "spans_between",
]

#: spans the process ring keeps: five 40 s windows of the busiest served
#: path (about 40k spans each at the block rates of one v5e)
CAPACITY = 1 << 18

_span_ids = itertools.count(1)
_request_ids = itertools.count(1)
_request: contextvars.ContextVar[int | None] = contextvars.ContextVar(
    "repro_obs_request", default=None
)


class Span(NamedTuple):
    """One closed span.  ``parent`` is the ``id`` of the span it was
    opened under on the same thread (None at the top); ``attrs`` is None
    or a dict of counts."""

    name: str
    t0: float
    t1: float
    parent: int | None
    request_id: int | None
    attrs: dict | None
    id: int

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


class RingWrapped(RuntimeError):
    """The ring overwrote spans of the interval asked for."""


class Ring:
    """A bounded, thread-safe store of closed spans, oldest overwritten
    first."""

    def __init__(self, capacity: int = CAPACITY):
        if capacity < 1:
            raise ValueError(f"capacity must be positive, got {capacity}")
        self.capacity = int(capacity)
        # closed spans as plain tuples in Span's field order (a tuple is
        # ten times cheaper to make than the named one); readers wrap them
        self._spans: deque[tuple] = deque(maxlen=self.capacity)
        self._lost_t1: float | None = None  # latest end of an overwritten span
        self._lock = threading.Lock()
        self._local = _Current()

    def span(self, name: str, **attrs) -> "_Open":
        """A context manager that records ``name`` in this ring."""
        return _Open(self, name, attrs or None)

    def _append(self, rec: tuple) -> None:
        with self._lock:
            spans = self._spans
            if len(spans) == self.capacity:
                lost = spans[0][2]
                if self._lost_t1 is None or lost > self._lost_t1:
                    self._lost_t1 = lost
            spans.append(rec)

    def spans_between(self, t0: float, t1: float) -> list[Span]:
        """The spans that opened at or after ``t0`` and closed at or
        before ``t1``, in the order they closed.  Raises
        :class:`RingWrapped` if the ring overwrote a span that closed at
        or after ``t0``."""
        with self._lock:
            if self._lost_t1 is not None and self._lost_t1 >= t0:
                raise RingWrapped(
                    f"the ring ({self.capacity} spans) overwrote spans "
                    f"closed at or after {t0!r}"
                )
            recs = list(self._spans)
        return [Span._make(r) for r in recs if r[1] >= t0 and r[2] <= t1]


class _Current(threading.local):
    """The id of the span open on this thread (None outside any)."""

    current: int | None = None


class _Open:
    """An open span; closes into its ring."""

    __slots__ = ("_ring", "name", "attrs", "id", "parent", "request_id", "t0", "_ann")

    def __init__(self, ring: Ring, name: str, attrs: dict | None):
        self._ring, self.name, self.attrs = ring, name, attrs

    def __enter__(self) -> "_Open":
        self._ann = TraceAnnotation(self.name)
        self._ann.__enter__()
        local = self._ring._local
        self.parent = local.current
        self.id = local.current = next(_span_ids)
        self.request_id = _request.get()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        t1 = time.perf_counter()
        ring = self._ring
        ring._local.current = self.parent
        ring._append(
            (self.name, self.t0, t1, self.parent, self.request_id, self.attrs,
             self.id)
        )
        self._ann.__exit__(*exc)
        return False


class _Request:
    __slots__ = ("id", "_token")

    def __enter__(self) -> int:
        self.id = next(_request_ids)
        self._token = _request.set(self.id)
        return self.id

    def __exit__(self, *exc) -> bool:
        _request.reset(self._token)
        return False


#: the process's ring, which :func:`span` records into
RING = Ring()


def span(name: str, **attrs) -> _Open:
    """A context manager that times one step under ``name`` into the
    process ring (and any running profiler trace)."""
    return _Open(RING, name, attrs or None)


def spanned(name: str):
    """Decorate a function so that each of its calls is one span
    ``name``."""

    def wrap(fn):
        @functools.wraps(fn)
        def call(*args, **kwargs):
            with _Open(RING, name, None):
                return fn(*args, **kwargs)

        return call

    return wrap


def request() -> _Request:
    """A context manager giving every span opened inside it, on this
    thread, one new request id (``with obs.request() as rid:``)."""
    return _Request()


def spans_between(t0: float, t1: float) -> list[Span]:
    """The process ring's spans inside ``[t0, t1]`` of the
    ``time.perf_counter()`` clock; see :meth:`Ring.spans_between`."""
    return RING.spans_between(t0, t1)


def self_times(records: Iterable[Span]) -> list[float]:
    """Each span's self time, in the order given: its duration less the
    part of its interval that its children among ``records`` cover
    (children that overlap each other are counted once)."""
    records = list(records)
    children: dict[int, list[tuple[float, float]]] = {}
    for r in records:
        if r.parent is not None:
            children.setdefault(r.parent, []).append((r.t0, r.t1))
    out = []
    for r in records:
        covered, end = 0.0, r.t0
        for s, e in sorted(children.get(r.id, ())):
            s, e = max(s, end), min(e, r.t1)
            if e > s:
                covered += e - s
                end = e
        out.append(r.seconds - covered)
    return out
