"""The in-program recorder (``repro.telemetry``) and where the served path
uses it: the recorder's own contract, the spans' twins in a profiler
trace, the named scopes in the lowered dispatches, and every span and
counter site firing in a small supervised run (so a site that is renamed
or lost fails here instead of reading as absent)."""
import glob
import os
import re
import sys
import tempfile
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from repro import telemetry
from repro.core.automaton import compile_query
from repro.core.engine import BatchedDenseRPQEngine, RegisteredQuery
from repro.core.executor import LocalExecutor, _delete_frontier, _ingest_frontier
from repro.streaming.service import PersistentQueryService
from repro.streaming.stream import SGT
from repro.streaming.supervisor import ServiceSupervisor

#: every span and counter the served path records (module doc of
#: repro.telemetry; PERF.md section 3 names the metric each serves)
SPANS = [
    "supervisor.batch", "supervisor.dispatch", "wal.append", "wal.fsync",
    "service.ingest", "service.expire", "engine.intern",
    "executor.dispatch", "executor.reserve",
    "executor.sync.flush_counts", "executor.sync.drain_dist",
    "executor.sync.drain_spill", "executor.sync.expire_live",
    "engine.result_wait", "engine.result_copy",
    "engine.decode", "engine.decode_scan",
    "checkpoint.capture", "checkpoint.write", "checkpoint.join",
]
COUNTERS = ["frontier.slab_rows", "frontier.rows_relaxed"]

INGEST_SCOPES = ["apply_batch", "frontier_seed", "pack_frontier",
                 "frontier_round", "dense_fallback", "emit_new",
                 "batched_valid_pairs"]
DELETE_SCOPES = ["drop_batch", "frontier_seed", "pack_frontier",
                 "frontier_round", "dense_fallback", "batched_valid_pairs"]
#: the row-sparse dist gathers the frontier rows once and scatters them
#: back once per dispatch (ingest), or only scatters (delete)
ROW_SPARSE_SCOPES = {"ingest": ["frontier_gather", "frontier_scatter"],
                     "delete": ["frontier_scatter"]}


def _make_executor():
    # small capacities, so that the spill ring and the dist overflow
    # table fill within a short stream and their drains run
    return LocalExecutor("jnp", frontier="auto", frontier_cap=4,
                         adj_layout="ell", ell_cap=2, spill_cap=2,
                         dist_layout="row_sparse", dist_cap=4)


def _make_service(**kw):
    svc = PersistentQueryService(window=6.0, slide=2.0,
                                 executor=_make_executor(), **kw)
    svc.register("q1", "a . b*", engine="dense", n_slots=16, batch_size=1)
    svc.register("q2", "(a | b)+", engine="dense", n_slots=16, batch_size=1)
    return svc


def _stream():
    edges = [(0, 1, "a"), (1, 2, "b"), (2, 3, "b"), (3, 1, "a"),
             (1, 4, "b"), (4, 0, "a"), (2, 5, "a"), (5, 6, "b")]
    out = [SGT(0.5 * i + 0.25, u, v, lab) for i, (u, v, lab)
           in enumerate(edges * 2)]
    # an explicit deletion of a live edge, after the slide boundaries
    # the timestamps above cross
    out.append(SGT(out[-1].ts + 0.001, 5, 6, "b", "-"))
    return out


def test_recorder_nesting_batch_ring_window_summary():
    rec = telemetry.Recorder(capacity=4)
    with rec.span("outer") as outer:
        with rec.batch(7):
            with rec.span("inner", 3) as inner:
                pass
        th = threading.Thread(target=lambda: rec.span("other").__enter__()
                              .__exit__(None, None, None))
        th.start()
        th.join()
    (o,), dropped = rec.records("outer")
    assert not dropped
    (i,), _ = rec.records("inner")
    assert o.parent is None and o.batch_id is None
    assert i.parent == "outer" and i.batch_id == 7 and i.value == 3
    assert outer.t0_ns <= inner.t0_ns <= inner.t1_ns <= outer.t1_ns
    assert (i.t0_ns, i.t1_ns) == (inner.t0_ns, inner.t1_ns)
    # another thread keeps its own stack: nothing of this one is its parent
    (other,), _ = rec.records("other")
    assert other.parent is None
    assert rec.current_batch() is None

    # the ring keeps the newest 4 of 6 records and flags the lost ones
    # only for an interval they ended in
    spans = []
    for k in range(6):
        with rec.span("ring", k) as sp:
            pass
        spans.append(sp)
    kept, dropped = rec.records("ring")
    assert [r.value for r in kept] == [2, 3, 4, 5] and dropped
    kept, dropped = rec.records("ring", since_ns=spans[4].t0_ns)
    assert [r.value for r in kept] == [4, 5] and not dropped
    kept, dropped = rec.records("ring", spans[2].t0_ns, spans[3].t0_ns)
    assert [r.value for r in kept] == [2, 3]
    assert rec.records("nothing") == ([], False)

    rec.count("rows", 5, t_ns=100)
    rec.count("rows", 7, t_ns=200)
    events, dropped = rec.records("rows", since_ns=150)
    assert [(e.t_ns, e.n) for e in events] == [(200, 7)] and not dropped
    summary = rec.summary()
    ring = summary["spans"]["ring"]
    assert ring["count"] == 6
    durs = sorted(s.t1_ns - s.t0_ns for s in spans)
    assert ring["max_ms"] == durs[-1] / 1e6
    assert ring["mean_ms"] == pytest.approx(sum(durs) / 6 / 1e6)
    assert summary["counters"]["rows"] == 12

    # threads record concurrently without losing a span or its time
    rec = telemetry.Recorder()
    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        workers = [threading.Thread(target=_spans_on_thread, args=(rec,))
                   for _ in range(16)]
        for w in workers:
            w.start()
        for w in workers:
            w.join(timeout=60)
        assert not any(w.is_alive() for w in workers)
    finally:
        sys.setswitchinterval(switch)
    stress, dropped = rec.records("stress")
    assert len(stress) == 16 * 500 and not dropped
    got = rec.summary()["spans"]["stress"]
    assert got["count"] == 16 * 500
    assert got["mean_ms"] == pytest.approx(
        sum(r.t1_ns - r.t0_ns for r in stress) / (16 * 500) / 1e6)


def _spans_on_thread(rec):
    for _ in range(500):
        with rec.span("stress"):
            pass


def test_spans_appear_in_a_profiler_trace():
    from jax.profiler import ProfileData

    svc = _make_service()
    stream = _stream()
    svc.ingest(stream[:4])      # compiles outside the trace
    with tempfile.TemporaryDirectory() as d:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0    # as the benchmark traces
        t0 = time.perf_counter_ns()
        jax.profiler.start_trace(d, profiler_options=opts)
        try:
            svc.ingest(stream[4:7])
        finally:
            jax.profiler.stop_trace()
        t1 = time.perf_counter_ns()
        (path,) = glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                            recursive=True)
        planes = ProfileData.from_file(path).planes
    traced = sorted(
        (ev.start_ns, -ev.duration_ns, ev.name[len("rpq."):], ev.duration_ns)
        for plane in planes if plane.name.startswith("/host")
        for line in plane.lines for ev in line.events
        if ev.name.startswith("rpq."))
    names, _counters = telemetry.names()
    recorded = sorted(
        (r.t0_ns, -(r.t1_ns - r.t0_ns), name, r.t1_ns - r.t0_ns)
        for name in names for r in telemetry.records(name, t0, t1)[0])
    assert len(recorded) > 10
    assert [r[2] for r in traced] == [r[2] for r in recorded]
    for (_s, _n, name, d_trace), (_t, _m, _name, d_rec) in zip(traced,
                                                                recorded):
        assert abs(d_trace - d_rec) <= max(0.1 * d_rec, 50_000), name


@pytest.mark.parametrize("layout", [("ell", "row_sparse"), ("dense", "dense")])
def test_lowered_dispatches_carry_named_scopes(layout):
    adj_layout, dist_layout = layout
    eng = BatchedDenseRPQEngine(
        [RegisteredQuery("q", compile_query("a . b*"), 10.0)], n_slots=16,
        batch_size=1, frontier="auto", frontier_cap=4,
        adj_layout=adj_layout, dist_layout=dist_layout)
    eng.insert_batch([(1, 2, "a", 1.0)])
    ex, t = eng.executor, eng.tables
    i = jnp.zeros((1,), jnp.int32)
    f = jnp.zeros((1,), jnp.float32)
    b = jnp.ones((1,), bool)
    tables = (t.btt, t.finals_mask, t.windows, t.live_mask, jnp.float32(10))
    static = dict(backend=ex.backend, f_cap=ex.frontier_cap)
    lowered = {
        "ingest": _ingest_frontier.lower(ex.arrays, i, i, i, f, b,
                                         jnp.float32(1), *tables, **static),
        "delete": _delete_frontier.lower(ex.arrays, i, i, i, b,
                                         jnp.float32(1), *tables, **static),
    }
    for op, low in lowered.items():
        text = low.as_text(debug_info=True)
        scopes = {part for loc in re.findall(r'loc\("([^"]*)"', text)
                  for part in loc.split("/")}
        want = INGEST_SCOPES if op == "ingest" else DELETE_SCOPES
        if dist_layout == "row_sparse":
            want = want + ROW_SPARSE_SCOPES[op]
        missing = [s for s in want if s not in scopes]
        assert not missing, f"{op}: {missing}"


def test_supervised_run_fires_every_span_and_counter():
    t0 = time.perf_counter_ns()
    with tempfile.TemporaryDirectory() as d:
        sup = ServiceSupervisor(_make_service, d, batch_events=4,
                                ckpt_every=2, drain_batches=1)
        sup.run(_stream())
        sup.wal.close()
    missing = [n for n in SPANS if not telemetry.records(n, t0)[0]]
    assert not missing, missing
    missing = [n for n in COUNTERS if not telemetry.records(n, t0)[0]]
    assert not missing, missing

    slab = sum(e.n for e in telemetry.records("frontier.slab_rows", t0)[0])
    relaxed = sum(e.n for e in
                  telemetry.records("frontier.rows_relaxed", t0)[0])
    assert 0 < relaxed <= slab

    rec = {n: telemetry.records(n, t0)[0] for n in SPANS}
    batches = rec["supervisor.batch"]
    assert [b.batch_id for b in batches] == list(
        range(batches[0].batch_id, batches[0].batch_id + len(batches)))
    # every span of the serving thread inside a batch carries its lsn
    for name in ("wal.append", "service.ingest", "executor.dispatch",
                 "engine.decode", "checkpoint.capture"):
        for r in rec[name]:
            owner = [b for b in batches if b.t0_ns <= r.t0_ns <= b.t1_ns]
            assert len(owner) == 1 and r.batch_id == owner[0].batch_id, name
    # the writer thread carries the lsn of the batch that took the snapshot
    assert {w.batch_id for w in rec["checkpoint.write"]} <= {
        c.batch_id for c in rec["checkpoint.capture"]}
    assert all(w.parent is None for w in rec["checkpoint.write"])
    for child, parent in [("wal.fsync", "wal.append"),
                          ("executor.reserve", "executor.dispatch"),
                          ("engine.decode_scan", "engine.decode"),
                          ("supervisor.dispatch", "supervisor.batch"),
                          ("service.ingest", "supervisor.dispatch")]:
        assert {r.parent for r in rec[child]} == {parent}, child
    assert all(r.value == 4 for r in rec["supervisor.batch"][:-1])
    assert all(r.value > 0 for r in rec["wal.append"])
    assert all(r.value > 0 for r in rec["engine.result_copy"])
    # the monitor reads the dispatch span, and keeps 32 samples at most
    assert len(sup.monitor.times) == min(len(rec["supervisor.dispatch"]), 32)
