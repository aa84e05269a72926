"""Spans and counters of the serving path, on the profiler's clock.

``span(name, counts=False, **attrs)`` times a block.  It enters a
``jax.profiler.TraceAnnotation`` of the same name, so a profile shows the
block on its host plane beside the device's operations (a flag check while
no profiler runs), and it appends one ``Span`` record to a bounded ring in
memory, profiler or not.  A record holds its ``perf_counter_ns`` start and
end and the id of the span that enclosed it; a span opened with ``counts``
also records how much every counter moved while it was open.  When the
ring is full the oldest record goes and the counter ``obs.dropped`` counts
it.

``add(name, n)`` moves a counter: a plain integer, moved once where the work
happens.  One ``jax.monitoring`` listener keeps backend-compile seconds per
``fun_name`` and the persistent cache's hits.

``snapshot()`` copies the counters; ``spans(lo, hi)`` returns the records
that lie inside a ``perf_counter`` interval; ``trace_ns`` maps a record's
time onto a profile's clock through one instant read on both.  Nothing is
written anywhere.
"""
from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax

RING_SIZE = 1 << 16
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
# recorded once per persistent-cache hit, beside ``cache_hits``
CACHE_HIT = "/jax/compilation_cache/compile_time_saved_sec"


class Span(NamedTuple):
    id: int
    parent: int                 # id of the enclosing span; -1 at the top
    name: str
    start: int                  # perf_counter_ns
    end: int
    attrs: dict
    counts: Optional[dict]      # counter moves, for a span with ``counts``

    @property
    def ns(self) -> int:
        return self.end - self.start


class _Open:
    __slots__ = ("rec", "name", "counts", "attrs", "ann", "id", "parent",
                 "base", "start")

    def __init__(self, rec: "Recorder", name: str, counts: bool,
                 attrs: dict):
        self.rec, self.name, self.counts, self.attrs = rec, name, counts, attrs

    # the clock is read next to the annotation's own reads, bookkeeping
    # outside them, so that a record and its annotation agree within µs
    def __enter__(self) -> "_Open":
        self.rec._push(self)
        self.ann = jax.profiler.TraceAnnotation(self.name)
        self.ann.__enter__()
        self.start = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        end = time.perf_counter_ns()
        self.ann.__exit__(*exc)
        self.rec._pop(self, end)


class Recorder:
    """The ring, the counters and the compile clock of one process."""

    def __init__(self, size: int = RING_SIZE):
        self.ring: collections.deque = collections.deque(maxlen=size)
        self.counters: Dict[str, int] = collections.defaultdict(int)
        self.compile_s: Dict[str, float] = collections.defaultdict(float)
        self._ids = itertools.count()
        self._lock = threading.Lock()
        self._local = threading.local()

    # -- spans ----------------------------------------------------------------
    def span(self, name: str, counts: bool = False, **attrs) -> _Open:
        return _Open(self, name, counts, attrs)

    def _push(self, op: _Open) -> None:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = [-1]
        op.parent = stack[-1]
        op.id = next(self._ids)
        stack.append(op.id)
        op.base = None
        if op.counts:
            with self._lock:
                op.base = dict(self.counters)

    def _pop(self, op: _Open, end: int) -> None:
        self._local.stack.pop()
        with self._lock:
            counts = None if op.base is None else {
                k: v - op.base.get(k, 0) for k, v in self.counters.items()
                if v != op.base.get(k, 0)}
            if len(self.ring) == self.ring.maxlen:
                self.counters["obs.dropped"] += 1
            self.ring.append(Span(op.id, op.parent, op.name, op.start, end,
                                  op.attrs, counts))

    def spans(self, lo: Optional[float] = None,
              hi: Optional[float] = None) -> List[Span]:
        """Records that start at or after ``lo`` and end by ``hi``
        (``perf_counter`` seconds), in the order they closed."""
        lo_ns = -1 if lo is None else round(lo * 1e9)
        hi_ns = float("inf") if hi is None else round(hi * 1e9)
        with self._lock:
            recs = list(self.ring)
        return [r for r in recs if r.start >= lo_ns and r.end <= hi_ns]

    # -- counters -------------------------------------------------------------
    def add(self, name: str, n: int = 1) -> None:
        with self._lock:
            self.counters[name] += n

    def on_compile(self, event: str, secs: float, fun_name: str = "?",
                   **_) -> None:
        """``jax.monitoring`` duration listener."""
        with self._lock:
            if event == BACKEND_COMPILE:
                self.compile_s[fun_name] += secs
            elif event == CACHE_HIT:
                self.counters["compile.cache_hits"] += 1

    def snapshot(self) -> dict:
        """Every counter by name, with ``compile.seconds``: a dict by the
        compiled function's name."""
        with self._lock:
            out = dict(self.counters)
            out["compile.seconds"] = dict(self.compile_s)
        return out


def trace_ns(t_ns: float, anchor: Tuple[float, float]) -> float:
    """``perf_counter_ns`` time ``t_ns`` on a profile's clock.  ``anchor``
    is one instant read on both clocks: (``perf_counter`` seconds, the
    profile's ns), such as the start of an annotation entered just before
    ``perf_counter`` was read."""
    perf_s, at_ns = anchor
    return at_ns + (t_ns - perf_s * 1e9)


def self_ns(parent: Span, records: Sequence[Span],
            names: Optional[Sequence[str]] = None) -> int:
    """``parent``'s duration less that of its children among ``records``
    (those named ``names``, or all): a thread's children do not overlap."""
    return parent.ns - sum(r.ns for r in records if r.parent == parent.id
                           and (names is None or r.name in names))


_REC = Recorder()
span = _REC.span
add = _REC.add
spans = _REC.spans
snapshot = _REC.snapshot
jax.monitoring.register_event_duration_secs_listener(_REC.on_compile)
