"""Supervision layer: WAL durability, crash-recovery identity under
injected faults, backpressure policies, and circuit-breaker degradation.

The load-bearing contract (ISSUE 10 acceptance): for EVERY injected fault
point — crash before dispatch, after dispatch, mid-snapshot (each stage of
the commit protocol), and during replay — restore + WAL-suffix replay
reproduces the exact per-batch result stream of an uninterrupted run, on
both executors and on a sparse layout combination. The supervisor itself
re-proves replayed batches inline (``verify_replay=True`` raises
:class:`ReplayDivergence` on any mismatch), and these tests additionally
compare the full chaos-run stream against a separately computed clean run.
"""
import os
import tempfile

import pytest

from repro.checkpoint import ckpt
from repro.streaming.generators import so_like, with_deletions
from repro.streaming.service import PersistentQueryService
from repro.streaming.stream import SGT, Stream
from repro.streaming.supervisor import (DENSE_FALLBACK_OVERRIDES,
                                        BoundedIngestQueue, CircuitBreaker,
                                        FaultPlan, ServiceSupervisor)
from repro.streaming.wal import WriteAheadLog

WINDOW, SLIDE = 20.0, 2.0


def _make_service(**overrides):
    kw = dict(window=WINDOW, slide=SLIDE)
    kw.update(overrides)
    svc = PersistentQueryService(**kw)
    svc.register("d_arb", "a2q . c2a*", engine="dense", n_slots=48)
    svc.register("d_plus", "(a2q | c2a)+", engine="dense", n_slots=48)
    svc.register("r_arb", "a2q . c2a*", engine="reference")
    return svc


def _stream_tuples():
    return list(with_deletions(so_like(24, 110, seed=13), ratio=0.04, seed=7))


def _clean_run(tuples, make_service, **sup_kwargs):
    with tempfile.TemporaryDirectory() as d:
        sup = ServiceSupervisor(make_service, d, **sup_kwargs)
        final = sup.run(list(tuples))
        return final, sup.result_stream(), sup.invalidation_stream()


# -- WAL ----------------------------------------------------------------------


def _mixed_batch(ts0):
    # vertex ids across types: int, str, tuple — the interner's encoding
    # must round-trip all of them
    return [SGT(ts0, 1, 2, "a2q"),
            SGT(ts0 + 0.1, "s1", ("p", 3), "c2a"),
            SGT(ts0 + 0.2, ("m", 4), 7, "c2q", "-")]


def test_wal_round_trip_typed_vertices():
    with tempfile.TemporaryDirectory() as d:
        wal = WriteAheadLog(d)
        b1, b2 = _mixed_batch(1.0), _mixed_batch(2.0)
        assert wal.append(b1) == 1
        assert wal.append(b2) == 2
        recs = list(wal.replay())
        assert [r.lsn for r in recs] == [1, 2]
        assert list(recs[0].events) == b1
        assert list(recs[1].events) == b2
        assert recs[0].clock == pytest.approx(1.2)
        wal.close()
        # a fresh instance over the same directory resumes sequencing
        wal2 = WriteAheadLog(d)
        assert wal2.last_lsn == 2
        assert wal2.append(_mixed_batch(3.0)) == 3
        assert [r.lsn for r in wal2.replay(after_lsn=1)] == [2, 3]


def test_wal_refuses_empty_batch():
    with tempfile.TemporaryDirectory() as d:
        with pytest.raises(ValueError):
            WriteAheadLog(d).append([])


def test_wal_torn_tail_is_skipped_and_truncated():
    with tempfile.TemporaryDirectory() as d:
        wal = WriteAheadLog(d)
        for i in range(3):
            wal.append(_mixed_batch(float(i)))
        wal.close()
        seg = os.path.join(d, wal._segments()[-1])
        size = os.path.getsize(seg)
        with open(seg, "r+b") as f:     # tear the last record mid-write
            f.truncate(size - 7)
        wal2 = WriteAheadLog(d)
        assert wal2.torn_records == 1
        assert wal2.last_lsn == 2       # the torn record never happened
        # recovery appends continue the sequence and replay reaches them
        # (the torn bytes were truncated away on reopen)
        assert wal2.append(_mixed_batch(9.0)) == 3
        assert [r.lsn for r in wal2.replay()] == [1, 2, 3]
        assert list(list(wal2.replay())[-1].events) == _mixed_batch(9.0)


def test_wal_crc_rejects_corruption():
    with tempfile.TemporaryDirectory() as d:
        wal = WriteAheadLog(d)
        wal.append(_mixed_batch(1.0))
        wal.append(_mixed_batch(2.0))
        wal.close()
        seg = os.path.join(d, wal._segments()[0])
        blob = open(seg, "rb").read()
        # flip one payload byte of the FIRST record: replay must stop
        # there (order after a bad record cannot be trusted), not skip it
        corrupted = blob[:20] + bytes([blob[20] ^ 0xFF]) + blob[21:]
        open(seg, "wb").write(corrupted)
        wal2 = WriteAheadLog(d)
        assert list(wal2.replay()) == []
        assert wal2.torn_records >= 1


def test_wal_rotation_and_truncate_upto():
    with tempfile.TemporaryDirectory() as d:
        wal = WriteAheadLog(d, segment_records=4)
        for i in range(10):
            wal.append(_mixed_batch(float(i)))
        assert len(wal._segments()) == 3
        # lsn 8 commits everything in the first two segments (1-4, 5-8)
        assert wal.truncate_upto(8) == 2
        assert [r.lsn for r in wal.replay()] == [9, 10]
        # covered-but-active segment is never unlinked
        assert wal.truncate_upto(10) == 0
        assert [r.lsn for r in wal.replay(after_lsn=9)] == [10]


def test_wal_churn_records_ride_the_sequence():
    with tempfile.TemporaryDirectory() as d:
        wal = WriteAheadLog(d)
        wal.append(_mixed_batch(1.0))
        wal.append_churn("register", "q_new",
                         {"expr": "a2q+", "kwargs": {"engine": "dense"}})
        wal.append(_mixed_batch(2.0))
        wal.append_churn("deregister", "q_new")
        kinds = [(r.lsn, r.kind) for r in wal.replay()]
        assert kinds == [(1, "batch"), (2, "register"),
                         (3, "batch"), (4, "deregister")]
        reg = list(wal.replay())[1]
        assert reg.meta["name"] == "q_new"
        assert reg.meta["expr"] == "a2q+"
        assert reg.meta["kwargs"] == {"engine": "dense"}
        with pytest.raises(ValueError):
            wal.append_churn("rename", "q_new")


# -- fault plan / queue / breaker ---------------------------------------------


def test_fault_plan_fires_exactly_once():
    plan = FaultPlan(crash_before_dispatch=[3], crash_mid_snapshot={1: "rename"},
                     slow_dispatch={2: 0.5}, transient_errors={4: 2})
    assert plan.take_crash("before_dispatch", 3)
    assert not plan.take_crash("before_dispatch", 3)   # retried lsn proceeds
    assert plan.take_snapshot_crash(1) == "rename"
    assert plan.take_snapshot_crash(1) is None
    assert plan.take_sleep(2) == 0.5
    assert plan.take_sleep(2) == 0.0
    assert plan.take_transient(4) and plan.take_transient(4)
    assert not plan.take_transient(4)                  # bounded
    assert plan.exhausted


def test_fault_plan_chaos_is_deterministic():
    a = FaultPlan.chaos(seed=11, n_batches=200, snapshot_crash_every=5)
    b = FaultPlan.chaos(seed=11, n_batches=200, snapshot_crash_every=5)
    assert a.__dict__ == b.__dict__
    c = FaultPlan.chaos(seed=12, n_batches=200)
    assert a.__dict__ != c.__dict__
    with pytest.raises(ValueError):
        FaultPlan(crash_mid_snapshot={1: "nonsense"})


def test_bounded_queue_policies():
    evt = [SGT(float(i), i, i + 1, "a2q") for i in range(8)]
    q = BoundedIngestQueue(cap=3, policy="block")
    assert all(q.push(e) for e in evt[:3])
    assert not q.push(evt[3])          # full: the producer must stall
    assert q.blocked == 1 and q.shed == 0
    q.take(1)
    assert q.push(evt[3])

    q = BoundedIngestQueue(cap=3, policy="shed-oldest")
    for e in evt[:5]:
        assert q.push(e)               # never refuses — drops the oldest
    assert q.shed == 2
    assert [s.src for s in q.take(3)] == [2, 3, 4]

    q = BoundedIngestQueue(cap=3, policy="shed-newest")
    for e in evt[:5]:
        assert q.push(e)
    assert q.shed == 2
    assert [s.src for s in q.take(3)] == [0, 1, 2]

    with pytest.raises(ValueError):
        BoundedIngestQueue(cap=0)
    with pytest.raises(ValueError):
        BoundedIngestQueue(cap=1, policy="random-early-drop")


def test_circuit_breaker_trip_and_rearm():
    br = CircuitBreaker(trip_threshold=0.25, rearm_after=2)
    assert br.observe(1, 10) is None          # 0.1 <= threshold: armed
    assert br.observe(5, 10) == "trip"        # 0.5 > threshold
    assert br.tripped
    assert br.observe(0, 10) is None          # quiet 1/2
    assert br.observe(3, 10) is None          # noisy: quiet run resets
    assert br.observe(0, 10) is None          # quiet 1/2
    assert br.observe(0, 10) == "rearm"       # quiet 2/2
    assert not br.tripped
    assert [a for _i, a, _r in br.log] == ["trip", "rearm"]


# -- crash-recovery identity (the acceptance criterion) -----------------------

CONFIGS = {
    "local-dense": {},
    "local-sparse": dict(frontier="on", frontier_cap=16, adj_layout="ell",
                         ell_cap=6, dist_layout="row_sparse", dist_cap=24),
    "mesh-dense": dict(executor="mesh"),
    "mesh-sparse": dict(executor="mesh", frontier="auto", frontier_cap=16,
                        adj_layout="ell", ell_cap=6,
                        dist_layout="row_sparse", dist_cap=24),
}

#: every fault point the issue names, in one plan: crash before dispatch,
#: crash after dispatch (results already recorded), crash mid-snapshot at
#: each stage of the commit protocol, crash DURING the recovery replay,
#: a straggler, and a transient error with retry
ALL_FAULT_POINTS = dict(
    crash_before_dispatch=[3], crash_after_dispatch=[7],
    crash_during_replay=[9],
    crash_mid_snapshot={1: "shards", 2: "manifest", 3: "rename"},
    slow_dispatch={5: 0.001}, transient_errors={6: 2})


@pytest.mark.parametrize("cfg_key", sorted(CONFIGS))
def test_crash_recovery_identity_all_fault_points(cfg_key):
    overrides = CONFIGS[cfg_key]

    def make(**extra):
        kw = dict(overrides)
        kw.update(extra)
        return _make_service(**kw)

    tuples = _stream_tuples()
    clean_final, clean_stream, clean_inval = _clean_run(
        tuples, make, batch_events=8, ckpt_every=4)

    with tempfile.TemporaryDirectory() as d:
        plan = FaultPlan(**ALL_FAULT_POINTS)
        sup = ServiceSupervisor(make, d, batch_events=8, ckpt_every=4,
                                fault_plan=plan, verify_replay=True)
        chaos_final = sup.run(list(tuples))
        assert plan.exhausted, "every scheduled fault must have fired"
        assert sup.restarts >= 4           # 2 dispatch + 3 snapshot crashes
        assert sup.recoveries, "at least one measured recovery"
        assert sup.retries >= 2            # the transient error retried
        # bit-identical per-batch result AND invalidation streams
        assert sup.result_stream() == clean_stream
        assert sup.invalidation_stream() == clean_inval
        assert chaos_final == clean_final
        for r in sup.recoveries:
            assert r.recovery_s >= 0.0
            assert r.replayed_events >= 0


def test_seeded_chaos_matrix_identity():
    """The CI chaos leg's shape: seeded random plans over the dense local
    config; every seed must preserve stream identity."""
    tuples = _stream_tuples()
    clean_final, clean_stream, _ = _clean_run(
        tuples, _make_service, batch_events=8, ckpt_every=4)
    for seed in (0, 1):
        with tempfile.TemporaryDirectory() as d:
            plan = FaultPlan.chaos(seed=seed, n_batches=14, crash_rate=0.2,
                                   transient_rate=0.2, straggler_s=0.0005)
            sup = ServiceSupervisor(_make_service, d, batch_events=8,
                                    ckpt_every=4, fault_plan=plan)
            assert sup.run(list(tuples)) == clean_final, seed
            assert sup.result_stream() == clean_stream, seed


def test_recovery_with_query_churn_in_wal():
    """Mid-stream register/deregister ride the WAL; a crash AFTER churn
    must reconstruct the churned query set (catalog from the checkpoint,
    suffix from the WAL) and keep the result stream identical."""
    tuples = _stream_tuples()

    def drive(sup):
        sup.run(list(tuples[:40]))
        sup.register("late", "c2a . a2q*", engine="dense", n_slots=48)
        sup.run(list(tuples[40:80]))
        sup.deregister("d_plus")
        sup.run(list(tuples[80:]))
        return sup.results()

    with tempfile.TemporaryDirectory() as d:
        clean = drive(ServiceSupervisor(_make_service, d, batch_events=8,
                                        ckpt_every=4))
    with tempfile.TemporaryDirectory() as d:
        # lsn 6 / 12 are the churn records themselves; 7 and 13 are the
        # first batches dispatched AFTER each churn op
        plan = FaultPlan(crash_before_dispatch=[7, 13],
                         crash_mid_snapshot={2: "rename"})
        sup = ServiceSupervisor(_make_service, d, batch_events=8,
                                ckpt_every=4, fault_plan=plan)
        chaos = drive(sup)
        assert plan.exhausted
        assert sup.restarts >= 3
    assert set(chaos) == set(clean)
    assert "late" in chaos and "d_plus" not in chaos
    for name in clean:
        assert chaos[name] == clean[name], name


def test_supervisor_gives_up_after_max_restarts():
    tuples = _stream_tuples()[:40]
    with tempfile.TemporaryDirectory() as d:
        # crash on the same lsn more times than the restart budget: each
        # recovery replays lsn 2 fine (fire-once) but the NEXT batch at
        # lsn 3, 4, ... keeps crashing
        plan = FaultPlan(crash_before_dispatch=[2, 3, 4, 5])
        sup = ServiceSupervisor(_make_service, d, batch_events=8,
                                ckpt_every=4, fault_plan=plan,
                                max_restarts=2)
        with pytest.raises(RuntimeError, match="restarts"):
            sup.run(list(tuples))


# -- backpressure -------------------------------------------------------------


def test_backpressure_block_policy_loses_nothing():
    tuples = _stream_tuples()
    clean_final, clean_stream, _ = _clean_run(
        tuples, _make_service, batch_events=8, ckpt_every=4)
    with tempfile.TemporaryDirectory() as d:
        sup = ServiceSupervisor(_make_service, d, batch_events=8,
                                ckpt_every=4, queue_cap=4,
                                queue_policy="block")
        # offer arrivals far faster than the per-tick drain capacity
        final = sup.run(list(tuples), arrival_chunk=64)
        assert sup.queue.blocked > 0       # the producer actually stalled
        assert sup.queue.shed == 0
        assert sup.queue.accepted == len(tuples)
        assert final == clean_final
        # grouping differs under pressure only if cap < batch; cap=4 <
        # batch_events=8 means batches of 4 — results stay identical as
        # SETS even though batch boundaries moved
        assert sup.wal.last_lsn >= len(clean_stream)


def test_backpressure_shed_policy_drops_explicitly():
    tuples = _stream_tuples()
    with tempfile.TemporaryDirectory() as d:
        sup = ServiceSupervisor(_make_service, d, batch_events=8,
                                ckpt_every=4, queue_cap=8,
                                queue_policy="shed-oldest", drain_batches=1)
        sup.run(list(tuples), arrival_chunk=len(tuples))  # one giant wave
        assert sup.queue.shed > 0
        assert sup.queue.high_water == 8
        # shed events never reached the WAL: the log holds exactly the
        # accepted-and-drained events, so replay stays self-consistent
        logged = sum(len(r.events) for r in sup.wal.replay())
        processed = sum(
            len(r.events)
            for lsn in sup.results_by_lsn
            for r in sup.wal.replay(after_lsn=lsn - 1) if r.lsn == lsn)
        assert processed <= logged


# -- circuit breaker / graceful degradation -----------------------------------


def _overflowy_service(**overrides):
    # capacities small enough that so_like's cyclic core overflows the
    # frontier AND the ELL rows AND the row-sparse dist rows constantly
    kw = dict(window=WINDOW, slide=SLIDE, frontier="on", frontier_cap=2,
              adj_layout="ell", ell_cap=2, dist_layout="row_sparse",
              dist_cap=4)
    kw.update(overrides)
    svc = PersistentQueryService(**kw)
    svc.register("d_arb", "a2q . c2a*", engine="dense", n_slots=48)
    svc.register("d_plus", "(a2q | c2a)+", engine="dense", n_slots=48)
    return svc


def test_breaker_trips_to_dense_and_preserves_results():
    tuples = _stream_tuples()
    clean_final, _, _ = _clean_run(tuples, _overflowy_service,
                                   batch_events=8, ckpt_every=4)
    with tempfile.TemporaryDirectory() as d:
        sup = ServiceSupervisor(
            _overflowy_service, d, batch_events=8, ckpt_every=4,
            health_every=2,
            breaker=CircuitBreaker(trip_threshold=0.5, rearm_after=10_000))
        final = sup.run(list(tuples))
        assert sup.breaker.tripped
        assert [a for _i, a, _r in sup.breaker.log] == ["trip"]
        # the live service is pinned to the dense fallbacks...
        assert sup._overrides == DENSE_FALLBACK_OVERRIDES
        ex = sup.service._group.executor
        assert ex.adjacency_stats["layout"] == "dense"
        assert ex.dist_stats["layout"] == "dense"
        assert sup.service._frontier == "off"
        # ...and the handover was loss-free (layouts are bit-identical)
        assert final == clean_final
        assert any(h.get("degraded") for h in sup.health_log)


def _roomy_sparse_service(**overrides):
    # capacities that never overflow this stream: every ELL/dist budget
    # drain finds nothing, no frontier falls back, nothing re-packs
    kw = dict(window=WINDOW, slide=SLIDE, frontier="on", frontier_cap=64,
              adj_layout="ell", ell_cap=64, dist_layout="row_sparse",
              dist_cap=128)
    kw.update(overrides)
    svc = PersistentQueryService(**kw)
    svc.register("d_arb", "a2q . c2a*", engine="dense", n_slots=48)
    svc.register("d_plus", "(a2q | c2a)+", engine="dense", n_slots=48)
    return svc


def test_breaker_ignores_empty_budget_drains():
    """Budget drains that find the spill ring / overflow table empty are
    bookkeeping syncs, not overflow: they must not trip the breaker."""
    with tempfile.TemporaryDirectory() as d:
        sup = ServiceSupervisor(
            _roomy_sparse_service, d, batch_events=8, ckpt_every=4,
            health_every=2, breaker=CircuitBreaker(trip_threshold=0.25))
        sup.run(_stream_tuples())
        ex = sup.service._group.executor
        assert ex.dist_stats["drains"] > 0
        assert ex.dist_stats["repacks"] == 0
        assert ex.adjacency_stats["repacks"] == 0
        assert ex.frontier_stats["fallbacks"] == 0
        assert not sup.breaker.log
        assert all(h["overflow_events"] == 0 for h in sup.health_log)


def test_breaker_rearms_after_quiet_period():
    tuples = _stream_tuples()
    clean_final, _, _ = _clean_run(tuples, _overflowy_service,
                                   batch_events=8, ckpt_every=4)
    with tempfile.TemporaryDirectory() as d:
        sup = ServiceSupervisor(
            _overflowy_service, d, batch_events=8, ckpt_every=4,
            health_every=2,
            breaker=CircuitBreaker(trip_threshold=0.5, rearm_after=1))
        final = sup.run(list(tuples))
        actions = [a for _i, a, _r in sup.breaker.log]
        assert actions[0] == "trip"
        assert "rearm" in actions          # dense intervals are quiet
        assert final == clean_final        # flapping never loses results
        marks = [h["breaker"] for h in sup.health_log]
        assert "tripped" in marks and "armed" in marks


# -- run_with_restarts port (satellite) ---------------------------------------


def test_run_service_with_restarts_port():
    from repro.distributed.fault import (StragglerMonitor,
                                         run_service_with_restarts)

    tuples = _stream_tuples()
    clean_final, _, _ = _clean_run(tuples, _make_service,
                                   batch_events=8, ckpt_every=4)
    slow_lsns = []
    with tempfile.TemporaryDirectory() as d:
        plan = FaultPlan(crash_before_dispatch=[4],
                         slow_dispatch={9: 0.05, 11: 0.05})
        results, report = run_service_with_restarts(
            _make_service, list(tuples), d,
            batch_events=8, ckpt_every=4, fault_plan=plan,
            on_straggler=slow_lsns.append,
            monitor=StragglerMonitor(deadline_factor=3.0, warmup=5))
        assert results == clean_final
        assert report["restarts"] == 1
        assert report["final_step"] == 14
        assert report["recoveries"] and report["recoveries"][0]["replay_eps"] > 0
        # straggler detection feeds both the callback and health telemetry
        assert report["stragglers"] == slow_lsns
        assert sum(h["stragglers"] for h in report["health_log"]) \
            >= len(slow_lsns) - 1  # tail interval may not have flushed
