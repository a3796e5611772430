"""The benchmark's own copies of the stream generators and query shapes.

Copied from ``repro.streaming.generators`` (``so_like``, ``yago_like``,
``with_deletions``, ``_DegreeTree``) and ``benchmarks/common.py``
(the paper's Table-2 queries and the SO label map), so that a change to
the program cannot move the yardstick. ``test_gen.py`` shows that these
copies draw the same streams as the originals for the cells' seeds and
sizes. Events are plain tuples ``(ts, src, dst, label, op)``; the harness
turns them into the program's input type.
"""
from __future__ import annotations

import random
import re
from typing import Dict, List, Tuple

Event = Tuple[float, object, object, str, str]

SO_LABELS = ["a2q", "c2a", "c2q"]

#: Table 2 of Pacaci, Bonifati & Oezsu (SIGMOD 2020): the most common
#: real-world RPQ shapes over three labels a, b, c
PAPER_QUERIES: Dict[str, str] = {
    "Q1": "a*",
    "Q2": "a . b*",
    "Q3": "a . b* . c*",
    "Q4": "(a | b | c)*",
    "Q5": "a . b* . c",
    "Q6": "a* . b*",
    "Q7": "a . b . c*",
    "Q8": "a? . b*",
    "Q9": "(a | b | c)+",
    "Q10": "(a | b | c) . b*",
    "Q11": "a . b . c",
}


def table2_queries(label_map: Dict[str, str]) -> Dict[str, str]:
    """The Table-2 shapes with a, b, c replaced by ``label_map`` at once
    (a sequential replace would re-match letters inside new labels)."""
    return {name: re.sub(r"[abc]", lambda m: label_map[m.group(0)], expr)
            for name, expr in PAPER_QUERIES.items()}


class _DegreeTree:
    """Integer vertex weights (all starting at 1) in a Fenwick tree: a
    preferential-attachment draw in O(log n), picking the same vertex as
    a linear scan over the prefix sums."""

    def __init__(self, n: int):
        self.n = n
        self.total = n
        self._tree = [i & -i for i in range(n + 1)]
        self._top = 1 << max(n.bit_length() - 1, 0)

    def add(self, i: int) -> None:
        self.total += 1
        i += 1
        while i <= self.n:
            self._tree[i] += 1
            i += i & -i

    def draw(self, rng: random.Random) -> int:
        r = rng.random() * self.total
        pos, acc, step = 0, 0, self._top
        while step:
            nxt = pos + step
            if nxt <= self.n and acc + self._tree[nxt] < r:
                pos = nxt
                acc += self._tree[nxt]
            step >>= 1
        return min(pos, self.n - 1)


def so_like(n_vertices: int, n_edges: int, seed: int,
            rate: float = 10.0) -> List[Event]:
    """StackOverflow-shaped stream: one vertex type, labels a2q/c2a/c2q,
    preferential attachment on both endpoints, Poisson timestamps."""
    rng = random.Random(seed)
    degree = _DegreeTree(n_vertices)
    out: List[Event] = []
    t = 0.0
    for _ in range(n_edges):
        t += rng.expovariate(rate)
        u = degree.draw(rng)
        v = degree.draw(rng)
        degree.add(u)
        degree.add(v)
        out.append((t, u, v, rng.choice(SO_LABELS), "+"))
    return out


def yago_like(n_vertices: int, n_edges: int, seed: int,
              n_labels: int = 100, rate: float = 10.0) -> List[Event]:
    """Yago2s-shaped stream: ``n_labels`` predicates at Zipf frequency over
    uniform endpoints, timestamps at a fixed rate."""
    rng = random.Random(seed)
    labels = [f"p{i}" for i in range(n_labels)]
    weights = [1.0 / (i + 1) for i in range(n_labels)]
    out: List[Event] = []
    t = 0.0
    for _ in range(n_edges):
        t += 1.0 / rate
        u = rng.randrange(n_vertices)
        v = rng.randrange(n_vertices)
        out.append((t, u, v, rng.choices(labels, weights)[0], "+"))
    return out


def with_deletions(events: List[Event], ratio: float,
                   seed: int) -> List[Event]:
    """Re-emit a fraction of earlier inserts as negative tuples 1 ms after
    the triggering insert (the paper's section 5.4 protocol), then order
    the whole stream by timestamp (stable), as the program's stream does."""
    rng = random.Random(seed)
    out: List[Event] = []
    inserted: List[Event] = []
    t_last = 0.0
    for e in events:
        out.append(e)
        inserted.append(e)
        t_last = e[0]
        if inserted and rng.random() < ratio:
            victim = inserted.pop(rng.randrange(len(inserted)))
            t_last += 1e-3
            out.append((t_last, victim[1], victim[2], victim[3], "-"))
    return sorted(out, key=lambda e: e[0])


GENERATORS = {"so_like": so_like, "yago_like": yago_like}
