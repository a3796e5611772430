"""In-program spans and counters of the persistent-RPQ service.

One recorder, always on, with no switch. Every layer of the served path
marks its work with it:

    with telemetry.span("engine.decode") as sp:
        ...
        sp.value = new_pairs

    telemetry.count("frontier.slab_rows", rows, t_ns=dispatch_ns)

A span does three things:

* enters ``jax.profiler.TraceAnnotation("rpq." + name)`` while the
  profiler is tracing, so that in a profiled run the span lies in the
  trace on the device timeline's clock;
* records ``(t0_ns, t1_ns, parent, batch_id, value)`` on
  ``time.perf_counter_ns`` in a ring of fixed capacity per name (and
  per thread: a thread writes only its own rings, so recording takes no
  lock);
* keeps the name's cumulative count, total and max.

``parent`` is the name of the innermost span open on the same thread
when the span started (each thread keeps its own stack, so the async
checkpoint writer nests under nothing of the serving thread).
``batch_id`` is the id the thread set with :func:`batch` (the
supervisor sets the WAL lsn of the batch it serves), so every span under
one batch carries the same id.

A counter event is ``(t_ns, n)``, stamped with the time of the work it
counts (a lazily flushed device counter carries its dispatch's time, not
the flush's).

:func:`records` returns a name's records in an interval of the
``perf_counter_ns`` clock and says whether the ring dropped any record
inside it; :func:`summary` is the operator's view. :data:`ANCHOR` pairs
one ``perf_counter_ns`` reading with the ``time_ns`` taken next to it at
import, so records can be joined offline with a trace or a log.

Nothing here may run inside a jit-traced function: a span there would
time the trace, once, and never the device's work.
"""
from __future__ import annotations

import collections
import math
import threading
import time
from typing import Deque, Dict, List, NamedTuple, Optional, Tuple

from jax.profiler import TraceAnnotation

#: records kept per span or counter name
RING_CAPACITY = 8192

#: (perf_counter_ns, time_ns) read together at import
ANCHOR: Tuple[int, int] = (time.perf_counter_ns(), time.time_ns())

_clock = time.perf_counter_ns
_tracing = TraceAnnotation.is_enabled
_thread_id = threading.get_ident


class SpanRecord(NamedTuple):
    t0_ns: int
    t1_ns: int
    parent: Optional[str]
    batch_id: Optional[int]
    value: Optional[float]


class CountRecord(NamedTuple):
    t_ns: int
    n: float


class _Ring:
    """The newest records of one name on one thread, with cumulative
    totals. ``n`` counts every record pushed; records arrive in end order
    (span end, counter stamp), so every evicted record ended no later
    than the oldest one kept."""

    __slots__ = ("records", "end", "n", "total", "max")

    def __init__(self, cap: int, end: int):
        self.records: Deque[tuple] = collections.deque(maxlen=cap)
        self.end = end          # index of a record's end time
        self.n = 0
        self.total = 0
        self.max = 0

    def dropped_since(self, lo: int) -> bool:
        """Whether a record that ended at or after ``lo`` may have been
        evicted (exact up to the oldest kept record's own end)."""
        return (self.n > len(self.records)
                and self.records[0][self.end] >= lo)


class _ThreadState:
    """One thread's span stack, batch id and rings: a thread writes only
    its own, so recording takes no lock."""

    __slots__ = ("stack", "batch_id", "spans", "counters")

    def __init__(self):
        self.stack: List[str] = []
        self.batch_id: Optional[int] = None
        self.spans: Dict[str, _Ring] = {}
        self.counters: Dict[str, _Ring] = {}


class Span:
    """One span (a context manager); ``value`` may be set before the
    block ends, and ``t0_ns``/``t1_ns``/``seconds`` read after it.
    Each :class:`Recorder` binds its own subclass (``Recorder.span``)."""

    __slots__ = ("name", "value", "t0_ns", "t1_ns", "_ann", "_st")
    _rec: "Recorder"
    _threads: Dict[int, _ThreadState]

    def __init__(self, name: str, value=None):
        self.name = name
        self.value = value

    def __enter__(self) -> "Span":
        st = self._threads.get(_thread_id())
        if st is None:
            st = self._rec._state()
        self._st = st
        st.stack.append(self.name)
        if _tracing():
            self._ann = TraceAnnotation("rpq." + self.name)
            self._ann.__enter__()
        else:
            self._ann = None
        self.t0_ns = _clock()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        t1 = self.t1_ns = _clock()
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        st = self._st
        stack = st.stack
        stack.pop()
        ring = st.spans.get(self.name)
        if ring is None:
            ring = st.spans[self.name] = _Ring(self._rec.capacity, 1)
        t0 = self.t0_ns
        ring.records.append((t0, t1, stack[-1] if stack else None,
                             st.batch_id, self.value))
        ring.n += 1
        d = t1 - t0
        ring.total += d
        if d > ring.max:
            ring.max = d

    @property
    def seconds(self) -> float:
        return (self.t1_ns - self.t0_ns) / 1e9


class _Batch:
    __slots__ = ("_st", "_id", "_prev")

    def __init__(self, st: _ThreadState, batch_id: Optional[int]):
        self._st = st
        self._id = batch_id

    def __enter__(self) -> None:
        self._prev = self._st.batch_id
        self._st.batch_id = self._id

    def __exit__(self, exc_type, exc, tb) -> None:
        self._st.batch_id = self._prev


class Recorder:
    """Span and counter rings of every thread (see module doc).
    ``span(name, value=None)`` opens a span."""

    def __init__(self, capacity: int = RING_CAPACITY):
        self.capacity = int(capacity)
        self._threads: Dict[int, _ThreadState] = {}
        self._lock = threading.Lock()
        self.span = type("Span", (Span,), {
            "__slots__": (), "_rec": self, "_threads": self._threads})

    def _state(self) -> _ThreadState:
        """This thread's state (a thread id the system reuses continues
        the rings of the thread that had it)."""
        st = self._threads.get(_thread_id())
        if st is None:
            with self._lock:
                st = self._threads.setdefault(_thread_id(), _ThreadState())
        return st

    def _rings(self, kind: str, name: str) -> List[_Ring]:
        with self._lock:
            states = list(self._threads.values())
        return [r for r in (getattr(st, kind).get(name) for st in states)
                if r is not None]

    def count(self, name: str, n, t_ns: Optional[int] = None) -> None:
        """Add ``n`` to counter ``name``, stamped ``t_ns`` (default now)."""
        t = _clock() if t_ns is None else int(t_ns)
        st = self._threads.get(_thread_id()) or self._state()
        ring = st.counters.get(name)
        if ring is None:
            ring = st.counters[name] = _Ring(self.capacity, 0)
        ring.records.append((t, n))
        ring.n += 1
        ring.total += n

    def batch(self, batch_id: Optional[int]) -> _Batch:
        """Context in which this thread's spans carry ``batch_id``."""
        return _Batch(self._state(), batch_id)

    def current_batch(self) -> Optional[int]:
        return self._state().batch_id

    def names(self) -> Tuple[List[str], List[str]]:
        """(span names, counter names) recorded so far."""
        with self._lock:
            states = list(self._threads.values())
        return (sorted({n for st in states for n in list(st.spans)}),
                sorted({n for st in states for n in list(st.counters)}))

    def records(self, name: str, since_ns: Optional[int] = None,
                until_ns: Optional[int] = None):
        """``(records, dropped)``: the span records of ``name`` whose start
        lies in ``[since_ns, until_ns]`` (or the counter events stamped
        there), by start, and whether a ring may have evicted a record
        that ended inside the interval (the list is then incomplete). An
        unknown name gives ``([], False)``."""
        lo = 0 if since_ns is None else since_ns
        hi = math.inf if until_ns is None else until_ns
        rings, kind = self._rings("spans", name), SpanRecord
        if not rings:
            rings, kind = self._rings("counters", name), CountRecord
        out = sorted((kind(*r) for ring in rings for r in list(ring.records)
                      if lo <= r[0] <= hi), key=lambda r: r[0])
        return out, any(ring.dropped_since(lo) for ring in rings)

    def summary(self) -> Dict[str, Dict[str, object]]:
        """Per span: count, mean, p95 and max in ms (count, mean and max
        cumulative; p95 over the records the rings hold). Per counter:
        the total."""
        span_names, counter_names = self.names()
        spans = {}
        for name in span_names:
            rings = self._rings("spans", name)
            n = sum(r.n for r in rings)
            durs = sorted(rec[1] - rec[0] for r in rings
                          for rec in list(r.records))
            p95 = durs[max(1, math.ceil(0.95 * len(durs))) - 1]
            spans[name] = {"count": n,
                           "mean_ms": sum(r.total for r in rings) / n / 1e6,
                           "p95_ms": p95 / 1e6,
                           "max_ms": max(r.max for r in rings) / 1e6}
        counters = {name: sum(r.total for r in self._rings("counters", name))
                    for name in counter_names}
        return {"spans": spans, "counters": counters}


#: the process's recorder
RECORDER = Recorder()
span = RECORDER.span
count = RECORDER.count
batch = RECORDER.batch
current_batch = RECORDER.current_batch
records = RECORDER.records
summary = RECORDER.summary
names = RECORDER.names
