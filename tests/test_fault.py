"""Fault tolerance of the persistent-query service: crash after a
mid-stream checkpoint, re-attach in a fresh service, and the re-attached
run must produce an IDENTICAL result stream to the uninterrupted one —
for the batched dense group AND the paper-faithful reference engines,
with explicit deletions in the stream.
"""
import os
import tempfile

import pytest

from repro.checkpoint import ckpt
from repro.streaming.generators import so_like, with_deletions
from repro.streaming.service import PersistentQueryService
from repro.streaming.stream import Stream

WINDOW, SLIDE = 20.0, 2.0


def _make_service(**kwargs):
    svc = PersistentQueryService(window=WINDOW, slide=SLIDE, **kwargs)
    svc.register("d_arb", "a2q . c2a*", engine="dense", n_slots=48)
    svc.register("d_plus", "(a2q | c2a)+", engine="dense", n_slots=48)
    svc.register("d_smp", "(a2q | c2a | c2q)*", engine="dense",
                 path_semantics="simple", n_slots=48)
    svc.register("r_arb", "a2q . c2a*", engine="reference")
    # (no reference RSPQ here: the paper's RSPQ listing has no Delete
    # algorithm, so it cannot ride a deletion stream)
    return svc


QUERY_NAMES = ["d_arb", "d_plus", "d_smp", "r_arb"]


def _stream_tuples():
    return list(with_deletions(so_like(24, 110, seed=13), ratio=0.04, seed=7))


def test_crash_restore_identical_result_stream():
    tuples = _stream_tuples()
    half = len(tuples) // 2

    # uninterrupted run: record the post-checkpoint NEW results per query
    svc = _make_service()
    svc.ingest(Stream(tuples[:half]))
    svc_next_expiry_at_ckpt = svc._next_expiry
    with tempfile.TemporaryDirectory() as ckpt_dir:
        svc.snapshot(ckpt_dir, step=half)
        mid_results = {name: svc.results(name) for name in QUERY_NAMES}
        tail_new = svc.ingest(Stream(tuples[half:]))
        final_results = {name: svc.results(name) for name in QUERY_NAMES}

        # crash: a brand-new service re-attaches and replays the tail
        svc2 = _make_service()
        step = svc2.restore(ckpt_dir)
        assert step == half
        # restored state matches the checkpoint moment exactly
        for name in QUERY_NAMES:
            assert svc2.results(name) == mid_results[name], name
        assert svc2._next_expiry == svc_next_expiry_at_ckpt
        tail_new2 = svc2.ingest(Stream(tuples[half:]))
        for name in QUERY_NAMES:
            # identical appended result stream (no loss, no duplicates) ...
            assert tail_new2[name] == tail_new[name], name
            # ... and identical final monotone sets
            assert svc2.results(name) == final_results[name], name
            assert svc2.stats[name].conflicted == svc.stats[name].conflicted


def test_restore_rejects_mismatched_query_set():
    tuples = _stream_tuples()[:40]
    svc = _make_service()
    svc.ingest(Stream(tuples))
    with tempfile.TemporaryDirectory() as ckpt_dir:
        svc.snapshot(ckpt_dir, step=1)
        svc2 = PersistentQueryService(window=WINDOW, slide=SLIDE)
        svc2.register("other", "a2q*", engine="dense", n_slots=48)
        with pytest.raises((ValueError, KeyError)):
            svc2.restore(ckpt_dir)


def test_register_after_ingest_is_live():
    """PR 2: late dense registrations re-pad the live group in place (no
    raise, no silent rebuild) — the new query immediately answers over the
    retained window, and the pre-existing queries keep their state."""
    svc = _make_service()
    svc.ingest(Stream(_stream_tuples()[:20]))
    before = {name: svc.results(name) for name in QUERY_NAMES}
    initial = svc.register("late", "a2q*", engine="dense")
    group = svc.queries["late"]
    lane = group.lane_of("late")
    # the initial result set IS the live-window snapshot for the new query
    assert initial == group.current_results(lane)
    assert svc.results("late") == initial
    # pre-existing queries are untouched by the arrival
    for name in QUERY_NAMES:
        assert svc.results(name) == before[name], name


def test_checkpoint_restore_with_churned_group():
    """Snapshot a group that grew by a LIVE registration (bucketed-Q
    padding), restore into a fresh service that registered the same final
    query set up-front (different lane layout): restore matches lanes by
    name and the tail result streams are identical."""
    tuples = _stream_tuples()
    half = len(tuples) // 2
    svc = _make_service()
    svc.ingest(Stream(tuples[:half]))
    svc.register("late", "a2q . c2q*", engine="dense")
    names = QUERY_NAMES + ["late"]
    with tempfile.TemporaryDirectory() as ckpt_dir:
        svc.snapshot(ckpt_dir, step=half)
        tail_new = svc.ingest(Stream(tuples[half:]))
        final = {name: svc.results(name) for name in names}

        svc2 = _make_service()
        svc2.register("late", "a2q . c2q*", engine="dense", n_slots=48)
        assert svc2.restore(ckpt_dir) == half
        tail_new2 = svc2.ingest(Stream(tuples[half:]))
        for name in names:
            assert tail_new2[name] == tail_new[name], name
            assert svc2.results(name) == final[name], name


# -- crash-mid-save hardening (ISSUE 10 satellite) ----------------------------


def test_crash_between_async_save_and_wait_pending_falls_back():
    """Kill the saver between `ckpt.async_save` and `wait_pending` at each
    stage of the commit protocol: `latest_step_dir` must NEVER surface a
    partial checkpoint. Publication is the LATEST swing — "shards" and
    "manifest" kills leave partial tmp dirs, and a "rename" kill leaves a
    complete-but-unpublished step dir; in every case restore falls back
    to the previously PUBLISHED step."""
    tuples = _stream_tuples()
    svc = _make_service()
    svc.ingest(Stream(tuples[:40]))
    with tempfile.TemporaryDirectory() as d:
        svc.snapshot(d, step=1)
        committed = ckpt.latest_step_dir(d)
        assert committed is not None and committed.endswith("step_000000001")
        mid_results = {name: svc.results(name) for name in QUERY_NAMES}
        tail_new = svc.ingest(Stream(tuples[40:]))

        for step, stage in ((2, "shards"), (3, "manifest")):
            svc.snapshot(d, step=step, async_save=True, _crash_after=stage)
            ckpt.wait_pending(d)  # deterministic stand-in for the kill
            # partial on-disk state exists (the crash left a tmp dir) ...
            assert any(".tmp" in n for n in os.listdir(d)), stage
            # ... but the read path never sees it
            assert ckpt.latest_step_dir(d) == committed, stage

        # restore lands on the previous committed step and the replayed
        # tail reproduces the uninterrupted result stream exactly
        svc2 = _make_service()
        assert svc2.restore(d) == 1
        for name in QUERY_NAMES:
            assert svc2.results(name) == mid_results[name], name
        tail_new2 = svc2.ingest(Stream(tuples[40:]))
        for name in QUERY_NAMES:
            assert tail_new2[name] == tail_new[name], name
            assert svc2.results(name) == svc.results(name), name

        # a kill after the commit rename but before the LATEST swing: the
        # step dir is complete on disk but UNPUBLISHED — recovery still
        # uses the previously published step (publication = LATEST swing,
        # so the commit point is one unambiguous instruction)
        svc.snapshot(d, step=4, async_save=True, _crash_after="rename")
        ckpt.wait_pending(d)
        assert os.path.isdir(os.path.join(d, "step_000000004"))
        assert ckpt.latest_step_dir(d) == committed
        svc3 = _make_service()
        assert svc3.restore(d) == 1
        for name in QUERY_NAMES:
            assert svc3.results(name) == mid_results[name], name


def test_sync_snapshot_joins_in_flight_async_save():
    """A synchronous snapshot to a directory with an async save still in
    flight (the breaker's handover lands on the same step as the periodic
    snapshot) joins that save first, so the two never write the same
    step's tmp dir at once; the later one is what LATEST publishes."""
    tuples = _stream_tuples()
    svc = _make_service()
    svc.ingest(Stream(tuples[:40]))
    with tempfile.TemporaryDirectory() as d:
        svc.snapshot(d, step=4, async_save=True)
        svc.snapshot(d, step=4)
        assert d not in ckpt._pending
        assert ckpt.latest_step_dir(d).endswith("step_000000004")
        assert not any(".tmp" in n for n in os.listdir(d))
        svc2 = _make_service()
        assert svc2.restore(d) == 4
        for name in QUERY_NAMES:
            assert svc2.results(name) == svc.results(name), name


# -- snapshot vs async-decode FIFO (ISSUE 10 satellite) -----------------------


def test_snapshot_drains_pending_async_decode_fifo():
    """`snapshot()` with a non-empty deferred-decode FIFO (async_depth>1)
    must drain it first: the in-flight dispatch has already mutated device
    state (emitted mask included), so saving before its results land in
    `per_query_results` would snapshot a mask ahead of the results —
    restore + replay would then silently DROP those pairs. After the
    drain, state and results agree: nothing dropped, nothing re-emitted."""
    tuples = _stream_tuples()
    svc = _make_service(async_decode=True, async_depth=4)
    svc.ingest(Stream(tuples[:60]))
    group = svc.queries["d_arb"]

    # dispatch a batch directly and leave its decode handle unresolved —
    # exactly the state an async_depth>1 pipeline is in mid-flight
    pending_batch = [(s.src, s.dst, s.label, s.ts)
                     for s in tuples[60:] if s.op == "+"][:8]
    handle = group.insert_batch_pending(pending_batch)
    assert len(group._pending_fifo) == 1

    with tempfile.TemporaryDirectory() as d:
        svc.snapshot(d, step=1)
        # the snapshot was a sequence point: FIFO drained, results landed
        assert len(group._pending_fifo) == 0
        after_snapshot = {name: svc.results(name) for name in QUERY_NAMES}
        # resolving the stale handle afterwards must be a no-op (already
        # decoded by the drain — no double-emit into the result sets)
        handle.resolve()
        assert {name: svc.results(name)
                for name in QUERY_NAMES} == after_snapshot

        # restore sees the in-flight batch's results (no drop) ...
        svc2 = _make_service(async_decode=True, async_depth=4)
        assert svc2.restore(d) == 1
        for name in QUERY_NAMES:
            assert svc2.results(name) == after_snapshot[name], name
        # ... and the two runs continue identically (no double-emit: a
        # re-emitted pair would show up in svc2's NEW stream but not svc's)
        rest = [s for s in tuples[60:]
                if (s.src, s.dst, s.label, s.ts) not in
                [tuple(b) for b in pending_batch]]
        tail_new = svc.ingest(Stream(rest))
        tail_new2 = svc2.ingest(Stream(rest))
        for name in QUERY_NAMES:
            assert tail_new2[name] == tail_new[name], name
            assert svc2.results(name) == svc.results(name), name
