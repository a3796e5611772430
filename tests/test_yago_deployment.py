"""The ``yago_like`` many-predicate stream at CPU size, on the service's
normal path, configured as ``benchmarks/stream_bench/configs/yago-table2.json``
configures it.

A ``yago_like`` stream (100 predicates at Zipf frequency, uniform
endpoints, 2% deletions) feeds ``PersistentQueryService`` with the eleven
Table-2 shapes over the three most frequent predicates p0-p2, frontier
``auto``, ELL adjacency, row-sparse dist and B = 1, as the deployment
runs them. Most events carry a predicate outside the queries' alphabet
and take the engine's bypass (no dispatch, a clock advance only), and
the stream touches several times more vertices than the group has slots,
so expiry recycles slots throughout. Call by call, the new and the
invalidated pairs of every query equal those of the reference engines
(``core/reference.py``'s ``RAPQ``) fed the same events.

Edges arrive at 8 per stream-second, so every timestamp is exact in the
engine's float32 clock as in the reference's float64 one."""
import time

import jax
import numpy as np
import pytest

from repro import telemetry
from repro.core import compile_query
from repro.core.reference import RAPQ
from repro.streaming.generators import with_deletions, yago_like
from repro.streaming.service import PersistentQueryService
from repro.streaming.stream import SGT, Stream

#: the Table-2 shapes (Pacaci, Bonifati & Oezsu, SIGMOD 2020) with
#: a, b, c -> p0, p1, p2, the deployment's label map
QUERIES = {
    "Q1": "p0*", "Q2": "p0 . p1*", "Q3": "p0 . p1* . p2*",
    "Q4": "(p0 | p1 | p2)*", "Q5": "p0 . p1* . p2", "Q6": "p0* . p1*",
    "Q7": "p0 . p1 . p2*", "Q8": "p0? . p1*", "Q9": "(p0 | p1 | p2)+",
    "Q10": "(p0 | p1 | p2) . p1*", "Q11": "p0 . p1 . p2",
}
ALPHABET = {"p0", "p1", "p2"}
WINDOW, SLIDE = 6.0, 1.0
N_VERTICES, N_EDGES, N_SLOTS = 300, 400, 64
CALL_EVENTS = 8           # events per service call, the supervisor's batch


def _stream(seed):
    return list(with_deletions(
        yago_like(N_VERTICES, N_EDGES, n_labels=100, seed=seed, rate=8.0),
        ratio=0.02, seed=seed))


def _service():
    svc = PersistentQueryService(
        window=WINDOW, slide=SLIDE, executor="local", frontier="auto",
        frontier_cap=8, adj_layout="ell", ell_cap=4,
        dist_layout="row_sparse", dist_cap=8)
    for name, expr in QUERIES.items():
        svc.register(name, expr, engine="dense", n_slots=N_SLOTS,
                     batch_size=1)
    return svc


class _Reference:
    """One ``RAPQ`` per query, driven as the service drives its reference
    engines: lazy expiry at slide boundaries before an event, and a
    deletion judged at its own clock."""

    def __init__(self):
        self.engines = {n: RAPQ(compile_query(e), WINDOW)
                        for n, e in QUERIES.items()}
        self.next_expiry = SLIDE

    def feed(self, events):
        new = {n: set() for n in QUERIES}
        invalidated = {n: set() for n in QUERIES}
        for e in events:
            if e.ts >= self.next_expiry:
                for eng in self.engines.values():
                    eng.expire(e.ts)
                while self.next_expiry <= e.ts:
                    self.next_expiry += SLIDE
            for n, eng in self.engines.items():
                if e.op == "+":
                    new[n] |= eng.insert(e.src, e.dst, e.label, e.ts)
                else:
                    eng.expire(e.ts)
                    invalidated[n] |= eng.delete(e.src, e.dst, e.label, e.ts)
        return new, invalidated


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_yago_stream_matches_reference_call_by_call(seed):
    events = _stream(seed)
    svc, ref = _service(), _Reference()
    t0 = time.perf_counter_ns()
    for i in range(0, len(events), CALL_EVENTS):
        call = events[i:i + CALL_EVENTS]
        report = svc.ingest(Stream(call))
        new, invalidated = ref.feed(call)
        for name in QUERIES:
            assert report[name] == new[name], (name, i)
            assert report.invalidated[name] == invalidated[name], (name, i)

    # the stream exercised what the deployment exercises: most events
    # bypassed the device, deletions reached it, slots were reused
    inserts = [e for e in events if e.op == "+"]
    outside = sum(1 for e in inserts if e.label not in ALPHABET)
    assert outside > len(inserts) // 2
    assert any(e.op == "-" and e.label in ALPHABET for e in events)
    touched = {v for e in inserts if e.label in ALPHABET
               for v in (e.src, e.dst)}
    assert len(touched) > 2 * N_SLOTS
    group = svc._group
    assert group.n_slots == N_SLOTS
    recycled = telemetry.records("engine.recycle", t0)[0]
    assert sum(r.value for r in recycled) > N_SLOTS
    assert any(svc.results(n) for n in QUERIES)


def _device_state(group):
    """The group's device state other than the stream clock, on the host."""
    a = group.executor.arrays
    return [np.asarray(x) for x in jax.tree_util.tree_leaves(
        (a.adj, a.dist, a.emitted))]


def test_bypass_advances_only_the_clock_and_expiry_still_recycles():
    """Chunks that pack no tuple (labels outside the alphabet, deletes of
    an outside label or of vertices never interned) leave the device
    state as it was but move its clock; the expiry they trigger still
    drops the window's edges and recycles their slots. The spans carry
    the chunk's event count and the dead-slot count."""
    svc = _service()
    graph = [(0, 1, "p0"), (1, 2, "p1"), (2, 3, "p1"), (4, 5, "p2"),
             (5, 6, "p0"), (6, 7, "p1")]
    svc.ingest(Stream([SGT(0.125 * (k + 1), u, v, lab)
                       for k, (u, v, lab) in enumerate(graph)]))
    assert (0, 3) in svc.results("Q2")
    group = svc._group
    before = _device_state(group)
    n_live = len(group.slot_of)
    assert n_live == 8

    # all-bypass calls inside the first slide: no expiry runs
    bypass = [SGT(0.875, 100, 101, "p7"), SGT(0.9, 3, 102, "p42"),
              SGT(0.925, 2, 3, "p9", "-"), SGT(0.95, 200, 201, "p0", "-"),
              SGT(0.975, 104, 105, "p99")]
    t0 = time.perf_counter_ns()
    report = svc.ingest(Stream(bypass))
    t1 = time.perf_counter_ns()
    assert not any(report.values()) and not any(report.invalidated.values())
    for got, want in zip(_device_state(group), before):
        np.testing.assert_array_equal(got, want)
    assert float(group.executor.arrays.now) == np.float32(0.975)
    assert len(group.slot_of) == n_live
    spans = telemetry.records("engine.bypass", t0, t1)[0]
    assert [r.value for r in spans] == [1] * len(bypass)
    assert all(t0 <= r.t0_ns <= r.t1_ns <= t1 for r in spans)
    assert not telemetry.records("engine.recycle", t0, t1)[0]

    # a bypassed event past the window: expiry drops every edge above
    # and recycles each of its vertices' slots
    late = WINDOW + 2.0
    t0 = time.perf_counter_ns()
    svc.ingest(Stream([SGT(late, 300, 301, "p50")]))
    t1 = time.perf_counter_ns()
    recycle = telemetry.records("engine.recycle", t0, t1)[0]
    assert [r.value for r in recycle] == [n_live]
    assert recycle[0].parent == "service.expire"
    assert not group.slot_of
    assert float(group.executor.arrays.now) == np.float32(late)
    # the expired p0 edge 0 -> 1 no longer starts a Q2 path through 3
    report = svc.ingest(Stream([SGT(late + 0.125, 3, 9, "p1")]))
    assert not report["Q2"]


def test_bypassed_counts_the_events_outside_the_alphabet_of_mixed_chunks():
    """With B = 2 a chunk can mix events inside and outside the alphabet:
    it dispatches and records no ``engine.bypass``, and its
    ``engine.intern`` span counts only the events inside; a chunk with
    none inside bypasses with both."""
    svc = PersistentQueryService(window=WINDOW, slide=SLIDE,
                                 frontier="auto", adj_layout="ell",
                                 dist_layout="row_sparse")
    svc.register("q", "p0 . p1*", engine="dense", n_slots=16, batch_size=2)
    svc.ingest(Stream([SGT(0.125, 0, 1, "p0")]))   # materialize the group
    group = svc._group
    t0 = time.perf_counter_ns()
    new = group.insert_batch([(1, 2, "p1", 0.25), (2, 3, "p5", 0.375),
                              (4, 5, "p8", 0.5), (5, 6, "p9", 0.625)])
    t1 = time.perf_counter_ns()
    assert new[group.lane_of("q")] == {(0, 2)}
    interns = telemetry.records("engine.intern", t0, t1)[0]
    assert [r.value for r in interns] == [1, 0]
    assert [r.value for r in telemetry.records("engine.bypass", t0, t1)[0]] \
        == [2]
