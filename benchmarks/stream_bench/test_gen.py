"""The benchmark's copies of the generators and queries draw exactly what
the program's originals draw, for the cells' seeds and sizes."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [HERE, os.path.join(ROOT, "src"), ROOT]

import gen  # noqa: E402
import workload  # noqa: E402

SEEDS = [0, 1, 7, 2**31 - 1, 2**31 + 12345]


def _program_events(stream):
    return [(s.ts, s.src, s.dst, s.label, s.op) for s in stream]


@pytest.mark.parametrize("seed", SEEDS)
def test_so_stream_matches_program(seed):
    from repro.streaming.generators import so_like, with_deletions

    cfg = workload.load_json(os.path.join(HERE, "configs", "so-table2.json"))
    p = cfg["stream"]
    n = 2400
    ours = gen.with_deletions(
        gen.so_like(p["n_vertices"], n, seed, rate=p["rate"]),
        p["deletion_ratio"], seed)
    theirs = with_deletions(
        so_like(p["n_vertices"], n, seed=seed, rate=p["rate"]),
        ratio=p["deletion_ratio"], seed=seed)
    assert ours == _program_events(theirs)


@pytest.mark.parametrize("seed", SEEDS)
def test_yago_stream_matches_program(seed):
    from repro.streaming.generators import with_deletions, yago_like

    cfg = workload.load_json(os.path.join(HERE, "configs",
                                          "yago-table2.json"))
    p = cfg["stream"]
    n = 2400
    ours = gen.with_deletions(
        gen.yago_like(p["n_vertices"], n, seed, n_labels=p["n_labels"],
                      rate=p["rate"]),
        p["deletion_ratio"], seed)
    theirs = with_deletions(
        yago_like(p["n_vertices"], n, n_labels=p["n_labels"], seed=seed,
                  rate=p["rate"]),
        ratio=p["deletion_ratio"], seed=seed)
    assert ours == _program_events(theirs)


def test_queries_match_program():
    from benchmarks.common import PAPER_QUERIES, so_queries

    assert gen.PAPER_QUERIES == PAPER_QUERIES
    assert gen.table2_queries({"a": "a2q", "b": "c2a", "c": "c2q"}) \
        == so_queries()


@pytest.mark.parametrize("config", ["so-table2", "yago-table2"])
def test_cell_body_is_prefix_stable(config):
    """The fill and the body a run offers are the same events whatever
    the body's length, so every seed and every mix sees one stream."""
    cfg = workload.load_json(os.path.join(HERE, "configs",
                                          config + ".json"))
    fill_a, body_a = workload.make_stream(cfg, {}, 99, 300)
    fill_b, body_b = workload.make_stream(cfg, {}, 99, 1200)
    assert fill_a == fill_b
    assert body_b[:300] == body_a
    assert all(e[0] > cfg["fill_stream_seconds"] for e in body_a)


def test_poisson_schedule_same_gaps_every_seed():
    traffic = {"arrivals": "poisson", "rate_eps": 6.0}
    a, backlog = workload.schedule(traffic, 1, 40.0)
    b, _ = workload.schedule(traffic, 2**31 + 5, 40.0)
    assert not backlog
    assert len(a) == len(b) and a != b
    assert a[-1] == pytest.approx(b[-1]) and a[-1] < 40.0
    gaps = lambda d: sorted(round(y - x, 9) for x, y in zip([0.0] + d, d))
    assert gaps(a) == gaps(b)


@pytest.mark.parametrize("seed", SEEDS)
def test_alphabet_share_is_fixed_in_every_prefix(seed):
    """Re-dealt Yago edges: every prefix of k edges holds floor(k * share)
    edges of the queries' alphabet, whatever the seed, with the seed's own
    edges of each kind in their own order."""
    import math

    cfg = workload.load_json(os.path.join(HERE, "configs",
                                          "yago-table2.json"))
    p = cfg["stream"]
    alphabet = set(cfg["label_map"].values())
    edges = gen.yago_like(p["n_vertices"], 3000, seed,
                          n_labels=p["n_labels"], rate=p["rate"])
    dealt = workload.fix_alphabet_share(edges, alphabet,
                                        p["alphabet_share"], 2000)
    assert [e[0] for e in dealt] == [e[0] for e in edges[:2000]]
    count = 0
    for k, e in enumerate(dealt):
        count += e[3] in alphabet
        assert count == math.floor((k + 1) * p["alphabet_share"])
    inside = [e[1:] for e in edges if e[3] in alphabet]
    assert [e[1:] for e in dealt if e[3] in alphabet] == inside[:count]
