"""Adversarial workload layer: generator contracts, and adaptive-controller
stability under hostile traffic (ISSUE 10 satellite — the ROADMAP's
"prove it survives production shapes" gap).

Stability here means the controllers SETTLE instead of thrashing:
``batch_size_log`` shows bounded direction changes (no sustained
grow/shrink oscillation), ``frontier_log``'s auto-cap only ever ratchets
up and stays bounded, and ``dist_log`` never reports lost entries while
its drain pressure stops growing — across bursty, churn-storm, and
deletion-heavy streams.
"""
import collections

import pytest

from repro.streaming.generators import (bursty_arrivals, churn_storm_plan,
                                        deletion_storm, mixed_window_streams,
                                        powerlaw_hotspot, so_like)
from repro.streaming.service import PersistentQueryService
from repro.streaming.stream import Stream

# -- generator contracts ------------------------------------------------------


def test_bursty_arrivals_contract():
    a = list(bursty_arrivals(32, 200, seed=3, flash_every=50, flash_len=16,
                             flash_boost=50.0))
    b = list(bursty_arrivals(32, 200, seed=3, flash_every=50, flash_len=16,
                             flash_boost=50.0))
    assert a == b                                # deterministic in the seed
    assert a != list(bursty_arrivals(32, 200, seed=4, flash_every=50))
    assert len(a) == 200
    assert all(x.ts < y.ts for x, y in zip(a, a[1:]))   # strictly increasing
    # flash crowds actually compress time: the minimum inter-arrival gap
    # inside a flash window is far below the off-flash median
    gaps = [y.ts - x.ts for x, y in zip(a, a[1:])]
    flash = sorted(gaps)[:16]
    assert max(flash) < sorted(gaps)[len(gaps) // 2] / 2


def test_powerlaw_hotspot_contract():
    a = list(powerlaw_hotspot(64, 300, seed=3, alpha=1.2))
    assert a == list(powerlaw_hotspot(64, 300, seed=3, alpha=1.2))
    assert len(a) == 300
    assert all(x.ts < y.ts for x, y in zip(a, a[1:]))
    # celebrity skew: the hottest source absorbs a far-above-uniform share
    counts = collections.Counter(s.src for s in a)
    assert counts.most_common(1)[0][1] / len(a) > 10.0 / 64


def test_deletion_storm_contract():
    base = so_like(24, 150, seed=5)
    storm = list(deletion_storm(base, storm_every=40, storm_len=16, seed=5))
    assert storm == list(deletion_storm(so_like(24, 150, seed=5),
                                        storm_every=40, storm_len=16, seed=5))
    assert all(x.ts < y.ts for x, y in zip(storm, storm[1:]))
    # every deletion targets a previously inserted, still-live edge
    live = set()
    n_del = 0
    for s in storm:
        key = (s.src, s.dst, s.label)
        if s.op == "+":
            live.add(key)
        else:
            n_del += 1
            assert key in live
            live.discard(key)
    # it IS deletion-heavy: storms delete in bursts, not a trickle
    assert n_del >= 0.15 * 150


def test_so_like_stream_pinned():
    """The O(log n) preferential-attachment draw reproduces the streams the
    linear-scan draw produced, bit for bit (values recorded from it)."""
    s = [(x.ts, x.src, x.dst, x.label, x.op)
         for x in so_like(1000, 2000, seed=5)]
    assert len(s) == 2000
    assert s[:3] == [(0.09752493692643936, 741, 795, "c2q", "+"),
                     (0.20308932552471554, 531, 840, "a2q", "+"),
                     (0.3077789815205892, 900, 113, "c2a", "+")]
    assert s[1995:] == [(195.4688926893313, 303, 835, "a2q", "+"),
                        (195.47629673826117, 591, 919, "a2q", "+"),
                        (195.55715434247006, 604, 498, "c2q", "+"),
                        (196.00579370645423, 622, 822, "c2q", "+"),
                        (196.03695899113814, 899, 176, "a2q", "+")]


def test_mixed_window_streams_span_100x():
    entries = mixed_window_streams(24, 60, seed=1)
    windows = [e["window"] for e in entries]
    assert max(windows) / min(windows) == pytest.approx(100.0)
    for e in entries:
        assert 0 < e["slide"] <= e["window"]
        assert len(list(e["stream"])) == 60


def test_churn_storm_plan_contract():
    plan = churn_storm_plan(80, seed=2, churn_every=8)
    assert plan == churn_storm_plan(80, seed=2, churn_every=8)
    live = set()
    for batch_idx, op, name, kind, expr in plan:
        assert 0 < batch_idx < 80
        if op == "register":
            assert name not in live and kind in ("rpq", "rapq") and expr
            live.add(name)
        else:
            assert op == "deregister" and name in live
            live.discard(name)
    # it is a storm: the live query set keeps shifting
    assert len(plan) >= 80 // 8 - 1


# -- adaptive-controller stability --------------------------------------------

WINDOW, SLIDE = 20.0, 2.0


def _adaptive_service():
    svc = PersistentQueryService(
        window=WINDOW, slide=SLIDE, adaptive_batch=True, max_batch=16,
        frontier="auto", frontier_cap=8,
        dist_layout="row_sparse", dist_cap=16)
    svc.register("q_arb", "a2q . c2a*", engine="dense", n_slots=48)
    svc.register("q_plus", "(a2q | c2a)+", engine="dense", n_slots=48)
    return svc


def _assert_controllers_settle(svc, regime):
    # batch sizing: power-of-two steps inside bounds, and bounded
    # direction changes — sustained grow/shrink/grow oscillation would
    # show up as many sign flips in the decision log
    sizes = [b for _seen, b in svc.batch_size_log]
    for b in sizes:
        assert 1 <= b <= svc._max_batch and (b & (b - 1)) == 0, regime
    flips = sum(1 for i in range(2, len(sizes))
                if (sizes[i] - sizes[i - 1]) * (sizes[i - 1] - sizes[i - 2]) < 0)
    assert flips <= 2, (regime, sizes)

    # frontier auto-cap: a pure ratchet (monotone non-decreasing), and it
    # settles instead of doubling forever
    caps = [e[1]["cap"] for e in svc.frontier_log if e[1].get("cap")]
    assert all(x <= y for x, y in zip(caps, caps[1:])), (regime, caps)
    if caps:
        assert caps[-1] <= caps[0] * 2 ** 4, (regime, caps)

    # row-sparse dist: overflow drains may fire but NOTHING is ever lost,
    # and per-interval drain pressure stops growing (the last third of the
    # run is no worse than the worst interval overall)
    assert all(e[1]["lost"] == 0 for e in svc.dist_log), regime
    drains = [e[1]["drains"] for e in svc.dist_log]
    deltas = [y - x for x, y in zip(drains, drains[1:])]
    if len(deltas) >= 3:
        tail = deltas[-(len(deltas) // 3):]
        assert max(tail) <= max(deltas), regime  # no late blow-up
        assert all(d >= 0 for d in deltas), regime


def test_stability_under_bursty_arrivals():
    svc = _adaptive_service()
    svc.ingest(Stream(list(bursty_arrivals(
        32, 260, seed=3, flash_every=60, flash_len=20, flash_boost=40.0))))
    assert svc.frontier_log and svc.dist_log
    _assert_controllers_settle(svc, "bursty")


def test_stability_under_deletion_storm():
    svc = _adaptive_service()
    svc.ingest(Stream(list(deletion_storm(
        so_like(24, 200, seed=5), storm_every=48, storm_len=20, seed=5))))
    assert svc.dist_log
    _assert_controllers_settle(svc, "deletion-storm")


def test_stability_under_query_churn_storm():
    svc = _adaptive_service()
    tuples = list(powerlaw_hotspot(48, 240, seed=7, alpha=1.1))
    plan = churn_storm_plan(len(tuples) // 8, seed=2, churn_every=6)
    ops = {b * 8: (op, name, expr) for b, op, name, _kind, expr in plan}
    done = 0
    for cut in sorted(ops) + [len(tuples)]:
        if cut > done:
            svc.ingest(Stream(tuples[done:cut]))
            done = cut
        if cut in ops:
            op, name, expr = ops[cut]
            if op == "register":
                svc.register(name, expr, engine="dense", n_slots=48)
            else:
                svc.deregister(name)
    assert svc.dist_log
    _assert_controllers_settle(svc, "churn-storm")


def test_stability_across_window_scales():
    """The same arrival process under window sizes spanning 100x: every
    scale keeps the no-loss dist contract and a ratcheting frontier."""
    for entry in mixed_window_streams(24, 140, seed=1):
        svc = PersistentQueryService(
            window=entry["window"], slide=entry["slide"],
            adaptive_batch=True, frontier="auto", frontier_cap=8,
            dist_layout="row_sparse", dist_cap=16)
        svc.register("q_arb", "a2q . c2a*", engine="dense", n_slots=48)
        svc.ingest(entry["stream"])
        _assert_controllers_settle(svc, entry["name"])
