"""Live query lifecycle conformance: registering / deregistering dense
queries AFTER ingestion has started (PR 2 tentpole).

Oracle construction for a query registered mid-stream:
`engine.make_churn_oracle` (shared with benchmarks/fig13_query_churn) — a
freshly built engine, clock-synced then fed the live group's retained
graph as one batch, then the tail per-tuple. Surviving queries are instead
held to their own uninterrupted history: Q independent engines replay the
FULL stream and every event's fresh results must match tuple-for-tuple
(churn of other queries must not perturb a member's stream).
"""
import random

import pytest

from repro.core import compile_query
from repro.core.engine import (
    BatchedDenseRPQEngine,
    DenseRPQEngine,
    RegisteredQuery,
    make_churn_oracle,
)
from repro.streaming.stream import SGT, Stream
from repro.streaming.service import PersistentQueryService

QUERIES = ["a*", "a . b*", "(a | b)*", "a . b* . c", "(a . b)+", "a . b . c"]
LABELS = ["a", "b", "c"]


def _random_stream(rng, n_vertices, n_edges, t_max):
    ts = sorted(rng.sample(range(1, t_max), k=min(n_edges, t_max - 1)))
    return [
        (rng.randrange(n_vertices), rng.randrange(n_vertices),
         rng.choice(LABELS), float(t))
        for t in ts
    ]


def _oracle_for(dfa, semantics, live_group, window, n_slots):
    return make_churn_oracle(dfa, live_group, window, n_slots,
                             path_semantics=semantics)


@pytest.mark.parametrize("seed", range(3))
def test_register_mid_stream_matches_fresh_oracle(seed):
    rng = random.Random(seed)
    window = 15.0
    base = [RegisteredQuery("q0", compile_query("a . b*"), window),
            RegisteredQuery("q1", compile_query("(a | b)*"), window)]
    group = BatchedDenseRPQEngine(base, n_slots=16, batch_size=1)
    indep = [DenseRPQEngine(s.dfa, window, n_slots=16, batch_size=1)
             for s in base]
    stream = _random_stream(rng, 6, 30, 80)
    cut = 15
    for i, (u, v, lab, ts) in enumerate(stream[:cut]):
        fresh = group.insert(u, v, lab, ts)
        for qi, eng in enumerate(indep):
            assert fresh[qi] == eng.insert(u, v, lab, ts), (seed, i, qi)
        if i % 7 == 6:
            group.expire(ts)
            for eng in indep:
                eng.expire(ts)

    dfa_new = compile_query("a*")
    oracle, oseed = _oracle_for(dfa_new, "arbitrary", group, window, 16)
    initial = group.register_query(RegisteredQuery("late", dfa_new, window))
    lane = group.lane_of("late")
    # the initial answer over the live window == the fresh oracle's seed
    assert initial == oseed, seed
    assert group.current_results(lane) == oracle.current_results()

    for i, (u, v, lab, ts) in enumerate(stream[cut:]):
        fresh = group.insert(u, v, lab, ts)
        assert fresh[lane] == oracle.insert(u, v, lab, ts), (seed, i)
        for qi, eng in enumerate(indep):
            # survivors: unperturbed by the arrival
            assert fresh[qi] == eng.insert(u, v, lab, ts), (seed, i, qi)
        if i % 7 == 6:
            group.expire(ts)
            oracle.expire(ts)
            for eng in indep:
                eng.expire(ts)
    assert group.per_query_results[lane] == oracle.results
    for qi, eng in enumerate(indep):
        assert group.per_query_results[qi] == eng.results


def test_deregister_keeps_survivors_and_reclaims_lane():
    rng = random.Random(7)
    window = 20.0
    specs = [RegisteredQuery(f"q{i}", compile_query(e), window)
             for i, e in enumerate(QUERIES[:3])]
    group = BatchedDenseRPQEngine(specs, n_slots=16, batch_size=1)
    indep = {i: DenseRPQEngine(s.dfa, window, n_slots=16, batch_size=1)
             for i, s in enumerate(specs)}
    stream = _random_stream(rng, 6, 30, 90)
    for (u, v, lab, ts) in stream[:12]:
        fresh = group.insert(u, v, lab, ts)
        for qi, eng in indep.items():
            assert fresh[qi] == eng.insert(u, v, lab, ts)

    cap_before = group.q_cap
    group.deregister_query("q1")
    del indep[1]
    assert group.n_queries == 2
    assert group.q_cap == cap_before          # capacity never shrinks
    assert group.current_results(1) == set()  # inert lane answers nothing

    for (u, v, lab, ts) in stream[12:20]:
        fresh = group.insert(u, v, lab, ts)
        assert fresh[1] == set()              # inert lane stays silent
        for qi, eng in indep.items():
            assert fresh[qi] == eng.insert(u, v, lab, ts)

    # re-registration reclaims the freed lane (no Q growth)
    dfa_new = compile_query("b . a*")
    oracle, oseed = _oracle_for(dfa_new, "arbitrary", group, window, 16)
    initial = group.register_query(RegisteredQuery("q3", dfa_new, window))
    assert group.lane_of("q3") == 1
    assert group.q_cap == cap_before
    assert initial == oseed
    for (u, v, lab, ts) in stream[20:]:
        fresh = group.insert(u, v, lab, ts)
        assert fresh[1] == oracle.insert(u, v, lab, ts)
        for qi, eng in indep.items():
            assert fresh[qi] == eng.insert(u, v, lab, ts)
    assert group.per_query_results[1] == oracle.results


def test_q_axis_bucket_growth():
    """Growing past the allocated lanes buckets the Q axis to the next
    multiple of 4; further registrations reclaim the padding lanes without
    reallocating."""
    window = 30.0
    group = BatchedDenseRPQEngine(
        [RegisteredQuery("q0", compile_query("a*"), window)],
        n_slots=8, batch_size=1)
    assert group.q_cap == 1
    group.insert(0, 1, "a", 1.0)
    group.register_query(RegisteredQuery("q1", compile_query("a . b*"), window))
    assert group.q_cap == 4                   # bucketed growth
    assert group.batched_arrays.dist.shape[0] == 4
    for i in range(2):
        group.register_query(
            RegisteredQuery(f"q{2 + i}", compile_query("b*"), window))
        assert group.q_cap == 4               # padding lanes reclaimed
    group.register_query(RegisteredQuery("q4", compile_query("(a|b)*"), window))
    assert group.q_cap == 8
    # all five queries answer; K grew to the deepest member
    assert group.n_queries == 5
    fresh = group.insert(1, 2, "b", 2.0)     # 0 -a-> 1 -b-> 2
    assert fresh[group.lane_of("q1")] == {(0, 2)}


def test_register_with_new_label_grows_alphabet():
    """A late query can bring labels outside the current union alphabet:
    the label axis grows append-only (existing adjacency rows keep their
    index) and the ×4-rounded label slots absorb small growth."""
    window = 50.0
    group = BatchedDenseRPQEngine(
        [RegisteredQuery("q0", compile_query("a*"), window)],
        n_slots=8, batch_size=1)
    group.insert(0, 1, "a", 1.0)
    assert group.batched_arrays.adj.shape[0] == 4  # 1 label, 4 slots
    group.register_query(
        RegisteredQuery("qd", compile_query("d . a*"), window))
    assert group.labels == ("a", "d")              # append-only
    lane = group.lane_of("qd")
    fresh = group.insert(5, 0, "d", 2.0)
    assert fresh[lane] == {(5, 0), (5, 1)}
    # grow past the 4 label slots
    group.register_query(
        RegisteredQuery("qmany", compile_query("e | f | g | h"), window))
    assert group.labels == ("a", "d", "e", "f", "g", "h")
    assert group.batched_arrays.adj.shape[0] == 8
    fresh = group.insert(7, 8, "g", 3.0)
    assert fresh[group.lane_of("qmany")] == {(7, 8)}
    # original query still answers over its own alphabet
    assert group.current_results(0) == {(0, 1)}


@pytest.mark.parametrize("seed", range(4))
def test_churn_conformance_randomized(seed):
    """Randomized streams with deletions and expiry, both path semantics:
    register + deregister mid-stream; survivors must match uninterrupted
    independent engines tuple-for-tuple, late queries their fresh-group
    oracles (insert, delete and snapshot views)."""
    rng = random.Random(100 + seed)
    window = rng.choice([10.0, 20.0, 40.0])
    specs = []
    for qi in range(3):
        expr = rng.choice(QUERIES)
        dfa = compile_query(expr)
        semantics = "arbitrary"
        if dfa.has_containment_property and rng.random() < 0.4:
            semantics = "simple"
        specs.append(RegisteredQuery(f"q{qi}", dfa, window, semantics))
    group = BatchedDenseRPQEngine(specs, n_slots=16, batch_size=1)
    indep = {qi: DenseRPQEngine(s.dfa, window, n_slots=16, batch_size=1,
                                path_semantics=s.path_semantics)
             for qi, s in enumerate(specs)}
    oracles = {}  # lane -> oracle engine for late registrations

    stream = _random_stream(rng, n_vertices=6, n_edges=26, t_max=70)
    live = {}
    events = []
    for (u, v, lab, ts) in stream:
        if live and rng.random() < 0.2:
            du, dv, dl = rng.choice(sorted(live))
            del live[(du, dv, dl)]
            events.append(("-", du, dv, dl, ts))
        else:
            live[(u, v, lab)] = ts
            events.append(("+", u, v, lab, ts))

    def lifecycle(step):
        if step == 8:
            expr = rng.choice(QUERIES)
            dfa = compile_query(expr)
            semantics = ("simple" if dfa.has_containment_property
                         and rng.random() < 0.5 else "arbitrary")
            oracle, oseed = _oracle_for(dfa, semantics, group, window, 16)
            initial = group.register_query(
                RegisteredQuery("late1", dfa, window, semantics))
            assert initial == oseed, (seed, expr)
            oracles[group.lane_of("late1")] = oracle
        elif step == 14:
            group.deregister_query("q1")
            del indep[1]
        elif step == 20:
            dfa = compile_query(rng.choice(QUERIES))
            oracle, oseed = _oracle_for(dfa, "arbitrary", group, window, 16)
            initial = group.register_query(
                RegisteredQuery("late2", dfa, window))
            lane = group.lane_of("late2")
            assert lane == 1, seed  # reclaimed the deregistered lane
            assert initial == oseed, seed
            oracles[lane] = oracle

    for i, (op, u, v, lab, ts) in enumerate(events):
        lifecycle(i)
        if op == "+":
            fresh = group.insert(u, v, lab, ts)
            for qi, eng in indep.items():
                assert fresh[qi] == eng.insert(u, v, lab, ts), (seed, i, qi)
            for lane, oracle in oracles.items():
                assert fresh[lane] == oracle.insert(u, v, lab, ts), (seed, i, lane)
        else:
            inv = group.delete(u, v, lab, ts)
            for qi, eng in indep.items():
                assert inv[qi] == eng.delete(u, v, lab, ts), (seed, i, qi)
            for lane, oracle in oracles.items():
                assert inv[lane] == oracle.delete(u, v, lab, ts), (seed, i, lane)
        if i % 7 == 6:
            group.expire(ts)
            for eng in indep.values():
                eng.expire(ts)
            for oracle in oracles.values():
                oracle.expire(ts)
        if i % 9 == 8:
            for qi, eng in indep.items():
                assert group.current_results(qi) == eng.current_results()
            for lane, oracle in oracles.items():
                assert group.current_results(lane) == oracle.current_results()

    for qi, eng in indep.items():
        assert group.per_query_results[qi] == eng.results, (seed, qi)
    for lane, oracle in oracles.items():
        assert group.per_query_results[lane] == oracle.results, (seed, lane)


def test_convergence_masking_reduces_query_rounds():
    """Mixed-depth group: the shallow query converges (and is masked out)
    rounds before the deep Kleene-star member, so the summed per-query
    active rounds sit strictly below the unmasked Q x global-rounds regime
    — with identical result streams."""
    window = 100.0
    specs = [RegisteredQuery("deep", compile_query("a*"), window),
             RegisteredQuery("shallow", compile_query("b"), window)]
    group = BatchedDenseRPQEngine(specs, n_slots=16, batch_size=1)
    indep = [DenseRPQEngine(s.dfa, window, n_slots=16, batch_size=1)
             for s in specs]
    edges = [(i, i + 1, "a", float(i + 1)) for i in range(10)]
    edges.append((0, 1, "b", 11.0))
    for (u, v, lab, ts) in edges:
        fresh = group.insert(u, v, lab, ts)
        for qi, eng in enumerate(indep):
            assert fresh[qi] == eng.insert(u, v, lab, ts)
    for qi, eng in enumerate(indep):
        assert group.per_query_results[qi] == eng.results
    assert group.total_query_rounds < group.n_queries * group.total_rounds, (
        group.total_query_rounds, group.total_rounds)


def test_service_live_lifecycle_and_invalidations():
    """Service level: live register answers immediately, deregister retires
    cleanly, and ingest() surfaces deletion invalidations alongside the new
    results (satellite fix: they were computed and discarded)."""
    svc = PersistentQueryService(window=100.0, slide=50.0)
    svc.register("d", "a . a*", engine="dense", n_slots=16)
    svc.register("r", "a . a*", engine="reference")
    rep = svc.ingest(Stream([SGT(1.0, 1, 2, "a"), SGT(2.0, 2, 3, "a")]))
    assert rep["d"] == {(1, 2), (2, 3), (1, 3)} == rep["r"]
    assert rep.invalidated["d"] == set() == rep.invalidated["r"]

    rep2 = svc.ingest(Stream([SGT(3.0, 2, 3, "a", "-")]))
    assert rep2["d"] == set()
    assert rep2.invalidated["d"] == {(2, 3), (1, 3)}
    assert rep2.invalidated["r"] == {(2, 3), (1, 3)}

    # live registration: initial answers over the retained window
    initial = svc.register("late", "a", engine="dense")
    assert initial == {(1, 2)}
    assert svc.results("late") == {(1, 2)}

    rep3 = svc.ingest(Stream([SGT(4.0, 3, 4, "a")]))
    assert rep3["late"] == {(3, 4)}

    svc.deregister("late")
    with pytest.raises(KeyError):
        svc.results("late")
    rep4 = svc.ingest(Stream([SGT(5.0, 4, 5, "a")]))
    assert rep4["late"] == set()          # history name stays, stream is dead
    assert (4, 5) in rep4["d"]            # survivors keep flowing
    assert svc.results("r") == svc.results("d")


def test_deletion_does_not_invalidate_window_expired_pairs():
    """A negative tuple between slide boundaries invalidates only pairs
    still valid at its own clock, on both engines: (1, 2) and (1, 3) fell
    out of the window (low = 2) before the delete, so only (2, 3) is
    reported, although the reference's lazy expiry still holds them."""
    svc = PersistentQueryService(window=10.0, slide=100.0)
    svc.register("d", "a . a*", engine="dense", n_slots=16)
    svc.register("r", "a . a*", engine="reference")
    svc.ingest(Stream([SGT(1.0, 1, 2, "a"), SGT(5.0, 2, 3, "a")]))
    rep = svc.ingest(Stream([SGT(12.0, 2, 3, "a", "-")]))
    assert rep.invalidated["d"] == {(2, 3)}
    assert rep.invalidated["r"] == {(2, 3)}


def test_first_dense_registration_mid_stream_starts_tracking():
    """The FIRST dense query arriving after ingestion started has no dense
    group to seed from (prefix content was only seen by reference engines):
    it is materialized EMPTY at registration — no silent deferral to the
    next ingest — and answers from that point of the stream on."""
    svc = PersistentQueryService(window=100.0, slide=50.0)
    svc.register("r", "a", engine="reference")
    svc.ingest(Stream([SGT(1.0, 1, 2, "a")]))
    initial = svc.register("late", "a", engine="dense", n_slots=16)
    assert initial == set()                 # nothing dense-side to seed from
    group = svc.queries["late"]
    assert group is not None and group.n_queries == 1  # live immediately
    rep = svc.ingest(Stream([SGT(2.0, 3, 4, "a")]))
    assert rep["late"] == {(3, 4)}
    assert svc.results("r") == {(1, 2), (3, 4)}
    # a SECOND dense query joins the (now existing) group seeded: it sees
    # the retained window including the edge the first one tracked
    initial2 = svc.register("late2", "a", engine="dense")
    assert initial2 == {(3, 4)}


def test_reregistered_name_keeps_stats_history():
    """deregister() promises the stats entry stays as history; re-using the
    name must not clobber it."""
    svc = PersistentQueryService(window=100.0, slide=50.0)
    svc.register("d", "a", engine="dense", n_slots=16)
    svc.ingest(Stream([SGT(1.0, 1, 2, "a")]))
    assert svc.stats["d"].tuples == 1
    svc.deregister("d")
    assert svc.stats["d"].tuples == 1       # history kept
    svc.register("d", "a . a*", engine="dense")
    assert svc.stats["d"].tuples == 1       # reuse does not reset history
    svc.ingest(Stream([SGT(2.0, 2, 3, "a")]))
    assert svc.stats["d"].tuples == 2


def test_service_checkpoint_records_live_query_set():
    """The manifest records the live query set lane-by-lane (None = inert
    padding), inspectable without restoring arrays."""
    import tempfile

    from repro.checkpoint import ckpt

    svc = PersistentQueryService(window=50.0, slide=10.0)
    svc.register("q0", "a*", engine="dense", n_slots=16)
    svc.ingest(Stream([SGT(1.0, 0, 1, "a")]))
    svc.register("q1", "a . b*", engine="dense")   # grows Q to a bucket of 4
    svc.deregister("q0")
    with tempfile.TemporaryDirectory() as d:
        svc.snapshot(d, step=3)
        extra = ckpt.manifest_extra(d)
        lanes = extra["dense"]["order"]
        assert lanes[1] == "q1" and lanes[0] is None
        assert extra["dense"]["labels"] == ["a", "b"]
        # restore into a differently-laid-out fresh service: matches by name
        svc2 = PersistentQueryService(window=50.0, slide=10.0)
        svc2.register("q1", "a . b*", engine="dense", n_slots=16)
        assert svc2.restore(d) == 3
        assert svc2.results("q1") == svc.results("q1")
