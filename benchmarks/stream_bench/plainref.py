"""Plain reference of windowed persistent RPQ evaluation, written for the
benchmark and importing nothing of the program.

Semantics (Pacaci, Bonifati & Oezsu, SIGMOD 2020, sections 2-3; arbitrary
path semantics, implicit window):

* the stream clock ``now`` is the largest timestamp seen so far; an edge
  is valid while its timestamp is above ``low = now - window``. Stream
  clocks and timestamps are float32, as the configuration states, so
  ``low`` is computed in float32 arithmetic;
* a pair ``(x, y)`` of query Q is valid when some walk of length >= 1
  from x to y over valid edges spells a word of L(Q); a repeated edge
  keeps its newest timestamp;
* an insert reports every valid pair that was never reported before (the
  result stream is append-only, each pair once);
* an explicit deletion removes the edge (every copy) and reports the pairs
  valid just before it and not just after, at the deletion's own clock;
* events whose label is in no query's alphabet only move the clock.

An insert can make new pairs valid only through the new edge, so only
those walks are searched: backwards from the edge's source, forwards from
its target, over the product of the graph with the query's automaton.
"""
from __future__ import annotations

import re
from typing import Dict, FrozenSet, Iterable, List, Set, Tuple

import numpy as np

Pair = Tuple[object, object]


# -- regular expressions -> position automaton (Glushkov) --------------------

_TOKEN = re.compile(r"\s*(?:([A-Za-z0-9_]+)|(.))")


def _tokens(expr: str) -> List[str]:
    out = []
    for m in _TOKEN.finditer(expr):
        if m.group(1):
            out.append("L:" + m.group(1))
        elif m.group(2) and not m.group(2).isspace():
            out.append(m.group(2))
    return out


class _Parser:
    """expr := term ('|' term)* ; term := factor (['.'] factor)* ;
    factor := atom ('*' | '+' | '?')* ; atom := label | '(' expr ')'."""

    def __init__(self, expr: str):
        self.toks = _tokens(expr)
        self.i = 0

    def peek(self):
        return self.toks[self.i] if self.i < len(self.toks) else None

    def take(self):
        tok = self.peek()
        self.i += 1
        return tok

    def parse(self):
        node = self.alt()
        if self.peek() is not None:
            raise ValueError(f"unexpected {self.peek()!r}")
        return node

    def alt(self):
        node = self.cat()
        while self.peek() == "|":
            self.take()
            node = ("alt", node, self.cat())
        return node

    def cat(self):
        node = self.factor()
        while self.peek() is not None and self.peek() not in ("|", ")"):
            if self.peek() == ".":
                self.take()
            node = ("cat", node, self.factor())
        return node

    def factor(self):
        node = self.atom()
        while self.peek() in ("*", "+", "?"):
            node = (self.take(), node)
        return node

    def atom(self):
        tok = self.take()
        if tok == "(":
            node = self.alt()
            if self.take() != ")":
                raise ValueError("missing ')'")
            return node
        if tok is None or not tok.startswith("L:"):
            raise ValueError(f"expected a label, got {tok!r}")
        return ("sym", tok[2:])


class Automaton:
    """Glushkov automaton: state 0 is the start, state i >= 1 the i-th
    label occurrence of the expression. ``step[(state, label)]`` lists the
    successor states; ``back[(state, label)]`` the predecessors."""

    def __init__(self, expr: str):
        self.labels: List[str] = []
        tree = _Parser(expr).parse()
        follow: Dict[int, Set[int]] = {}
        nullable, first, last = self._walk(tree, follow)
        self.n_states = len(self.labels) + 1
        self.finals: FrozenSet[int] = frozenset(
            set(last) | ({0} if nullable else set()))
        self.step: Dict[Tuple[int, str], List[int]] = {}
        for p in first:
            self.step.setdefault((0, self.labels[p - 1]), []).append(p)
        for p, nxt in follow.items():
            for q in nxt:
                self.step.setdefault((p, self.labels[q - 1]), []).append(q)
        self.back: Dict[Tuple[int, str], List[int]] = {}
        for (p, lab), qs in self.step.items():
            for q in qs:
                self.back.setdefault((q, lab), []).append(p)
        self.alphabet = frozenset(self.labels)

    def _walk(self, node, follow):
        kind = node[0]
        if kind == "sym":
            self.labels.append(node[1])
            p = len(self.labels)
            return False, {p}, {p}
        if kind in ("alt", "cat"):
            n1, f1, l1 = self._walk(node[1], follow)
            n2, f2, l2 = self._walk(node[2], follow)
            if kind == "alt":
                return n1 or n2, f1 | f2, l1 | l2
            for p in l1:
                follow.setdefault(p, set()).update(f2)
            return (n1 and n2, f1 | f2 if n1 else f1, l1 | l2 if n2 else l2)
        n, f, l_ = self._walk(node[1], follow)
        if kind in ("*", "+"):
            for p in l_:
                follow.setdefault(p, set()).update(f)
        return (n or kind in ("*", "?")), f, l_


# -- the reference engine ------------------------------------------------------


def f32(x: float) -> float:
    return float(np.float32(x))


class PlainRPQ:
    """Every registered query over one windowed edge stream."""

    def __init__(self, queries: Dict[str, str], window: float):
        self.autos = {name: Automaton(expr) for name, expr in queries.items()}
        self.window32 = np.float32(window)
        self.alphabet = frozenset().union(
            *(a.alphabet for a in self.autos.values()))
        self.now32 = np.float32(-np.inf)
        #: (u, v, label) -> newest float32 timestamp, valid or not yet pruned
        self.edge_ts: Dict[Tuple[object, object, str], float] = {}
        self.out_adj: Dict[object, Dict[Tuple[object, str], float]] = {}
        self.in_adj: Dict[object, Dict[Tuple[object, str], float]] = {}
        self.reported: Dict[str, Set[Pair]] = {n: set() for n in self.autos}
        self._pruned_at = float("-inf")

    # -- clock and graph -------------------------------------------------------

    def _advance(self, ts: float) -> None:
        self.now32 = max(self.now32, np.float32(ts))

    @property
    def low(self) -> float:
        return float(np.float32(self.now32 - self.window32))

    def _prune(self) -> None:
        """Drop edges that can never be valid again (a window's worth at a
        time, so the cost stays linear)."""
        low = self.low
        if low - self._pruned_at < float(self.window32) / 4:
            return
        for key in [k for k, t in self.edge_ts.items() if t <= low]:
            self._remove(key)
        self._pruned_at = low

    def _remove(self, key) -> None:
        u, v, lab = key
        del self.edge_ts[key]
        del self.out_adj[u][(v, lab)]
        del self.in_adj[v][(u, lab)]

    # -- searches over the product graph ----------------------------------------

    def _backward(self, auto: Automaton, u, s: int, low: float,
                  skip=None) -> Set[object]:
        """Vertices x with a valid walk x ->* u that leads the automaton
        from its start to state s (the empty walk when x = u, s = 0)."""
        seen = {(u, s)}
        stack = [(u, s)]
        out = set()
        while stack:
            w, r = stack.pop()
            if r == 0:
                out.add(w)
            for (w2, lab), ts in self.in_adj.get(w, {}).items():
                if ts <= low or (w2, w, lab) == skip:
                    continue
                for r2 in auto.back.get((r, lab), ()):
                    node = (w2, r2)
                    if node not in seen:
                        seen.add(node)
                        stack.append(node)
        return out

    def _forward(self, auto: Automaton, starts: Iterable[Tuple[object, int]],
                 low: float, skip=None) -> Set[object]:
        """Vertices y reached in a final state from any of ``starts`` (the
        starts themselves count when final)."""
        seen = set(starts)
        stack = list(seen)
        out = set()
        while stack:
            w, r = stack.pop()
            if r in auto.finals:
                out.add(w)
            for (w2, lab), ts in self.out_adj.get(w, {}).items():
                if ts <= low or (w, w2, lab) == skip:
                    continue
                for r2 in auto.step.get((r, lab), ()):
                    node = (w2, r2)
                    if node not in seen:
                        seen.add(node)
                        stack.append(node)
        return out

    def _through(self, auto: Automaton, u, v, lab: str,
                 low: float) -> Set[Pair]:
        """Valid pairs with a walk that uses the edge (u, v, lab)."""
        pairs: Set[Pair] = set()
        for s in range(auto.n_states):
            nxt = auto.step.get((s, lab))
            if not nxt:
                continue
            xs = self._backward(auto, u, s, low)
            if not xs:
                continue
            ys = self._forward(auto, [(v, t) for t in nxt], low)
            pairs.update((x, y) for x in xs for y in ys)
        return pairs

    def _valid_from(self, auto: Automaton, x, low: float, skip) -> Set[object]:
        """Targets y of valid walks of length >= 1 from x, never using the
        edge ``skip``."""
        starts = []
        for (w2, lab), ts in self.out_adj.get(x, {}).items():
            if ts <= low or (x, w2, lab) == skip:
                continue
            starts.extend((w2, t) for t in auto.step.get((0, lab), ()))
        return self._forward(auto, starts, low, skip)

    # -- events ---------------------------------------------------------------

    def insert(self, u, v, lab: str, ts: float) -> Dict[str, Set[Pair]]:
        self._advance(ts)
        new: Dict[str, Set[Pair]] = {n: set() for n in self.autos}
        if lab not in self.alphabet:
            return new
        key = (u, v, lab)
        t32 = f32(ts)
        if t32 >= self.edge_ts.get(key, float("-inf")):
            self.edge_ts[key] = t32
            self.out_adj.setdefault(u, {})[(v, lab)] = t32
            self.in_adj.setdefault(v, {})[(u, lab)] = t32
        low = self.low
        if t32 <= low:
            return new
        for name, auto in self.autos.items():
            if lab not in auto.alphabet:
                continue
            fresh = self._through(auto, u, v, lab, low) - self.reported[name]
            self.reported[name] |= fresh
            new[name] = fresh
        self._prune()
        return new

    def delete(self, u, v, lab: str, ts: float) -> Dict[str, Set[Pair]]:
        self._advance(ts)
        inv: Dict[str, Set[Pair]] = {n: set() for n in self.autos}
        key = (u, v, lab)
        if key not in self.edge_ts:
            return inv
        low = self.low
        if self.edge_ts[key] > low:
            for name, auto in self.autos.items():
                if lab not in auto.alphabet:
                    continue
                cands = self._through(auto, u, v, lab, low)
                by_src: Dict[object, Set[object]] = {}
                for x, y in cands:
                    by_src.setdefault(x, set()).add(y)
                for x, ys in by_src.items():
                    still = self._valid_from(auto, x, low, skip=key)
                    inv[name].update((x, y) for y in ys - still)
        self._remove(key)
        return inv

    def apply(self, event) -> Tuple[Dict[str, Set[Pair]], Dict[str, Set[Pair]]]:
        """One ``(ts, src, dst, label, op)`` event -> (new, invalidated)."""
        ts, u, v, lab, op = event
        empty = {n: set() for n in self.autos}
        if op == "+":
            return self.insert(u, v, lab, ts), empty
        return empty, self.delete(u, v, lab, ts)
