"""Persistent-query service: the end-to-end serving driver.

Register RPQs (with per-query engine choice + path semantics), ingest an
ordered sgt stream with eager evaluation and lazy expiration (slide
interval β), and emit an append-only result stream per query — exactly the
paper's execution model (§2, §5.1).

Multi-query execution: every query registered with ``engine="dense"`` is
folded into ONE :class:`~repro.core.engine.BatchedDenseRPQEngine` sharing
the labeled adjacency and the vertex interner, so each arriving sgt costs a
single jitted dispatch for the whole dense workload instead of one per
query (benchmarks/fig12_multi_query.py measures the win). Reference
engines (the paper-faithful pointer oracles) stay on the per-query path.

Executor selection (PR 3): the service chooses the dense group's device
path — ``executor="local"`` (single device, the default) or
``executor="mesh"`` (Q lanes sharded over the process's device mesh with
convergence-aware dispatch, :mod:`repro.distributed.executor`); an
:class:`~repro.core.executor.Executor` instance is also accepted. Result
streams are identical across executors (tests/test_executor.py).

Async result decode (PR 3, deepened PR 4): with ``async_decode=True`` the
service defers the device→host transfer of each ingest's emit frontier
behind a bounded FIFO of up to ``async_depth`` in-flight dispatches — the
transfer of dispatch *i* overlaps dispatches *i+1..i+k* instead of
blocking the hot path (engine :class:`~repro.core.engine.PendingResults`;
decode safety is preserved by per-dispatch interner snapshots and strict
FIFO drain order, and all handles resolve before any expiry, deletion,
lifecycle event, or the end of :meth:`ingest`, so the returned report is
complete).

Adaptive micro-batching (PR 4, opt-in ``adaptive_batch=True``): dense
inserts buffer into micro-batches whose size doubles/halves (power-of-two
bucketing, capped at ``max_batch``) from the executor's skip counters at
each slide boundary — a large no-op relaxation tail means dispatch
overhead dominates useful work, so the batch grows; decisions land in
``batch_size_log`` and B > 1 carries the engine's documented
batch-boundary skew.

Contraction backends (PR 4): dense registrations accept ``backend`` as a
name ("jnp" | "pallas" | "mxu_bucket") or a
:class:`~repro.core.backend.ContractionBackend` instance, validated AT
REGISTRATION (unknown names raise with the known list — they used to fall
back to jnp silently). Both executors run the selected backend.

RSPQ fallback (PR 3): a dense lane running ``path_semantics="simple"``
over-approximates when its automaton lacks the containment property and a
conflict materializes (Definition 16). When ``per_query_conflicted`` fires
for such a lane, the service routes the query to the exact (paper §4.1)
reference RSPQ engine, seeded from the group's
:meth:`~repro.core.engine.BatchedDenseRPQEngine.retained_edges` — the
switch is surfaced in :attr:`IngestReport.fallbacks`, the lane returns to
the group as reclaimable padding, and results from the switch on are
exact (results emitted before the switch may over-report; that window is
exactly what the flag marks). Disable with ``rspq_fallback=False`` to keep
the flag-only PR 2 behavior.

Query lifecycle is LIVE (PR 2): :meth:`PersistentQueryService.register`
works before OR after ingestion has started — a late dense registration
re-pads the running group's device state in place and seeds the new
query's closure over the retained graph, so it immediately answers over
the current window (the initial result pairs are returned).
:meth:`deregister` retires a query mid-stream; its lane becomes inert
padding reclaimed by the next registration. A dense query registered after
ingestion adopts the group's existing capacities (``n_slots``,
``batch_size``, ``backend``) — per-call capacity arguments apply only
while the group is still unmaterialized. Vertex capacity grows on demand
(PR 3), so ``n_slots`` is a starting size, not a ceiling.

Deletion visibility: :meth:`ingest` returns an :class:`IngestReport` — a
plain ``dict`` of NEW result pairs per query (backward compatible) whose
``.invalidated`` attribute carries the result pairs each negative tuple
invalidated (the paper's §3.2 invalidation stream) and whose
``.fallbacks`` attribute names the queries switched to the reference RSPQ
path during the call.

Fault tolerance: the service checkpoints engine state via
checkpoint/ckpt.py — the batched dense group as one pytree of device
arrays + interner/result metadata in the manifest (the manifest records
the LIVE query set lane-by-lane and the label order), reference engines as
pickled leaves — and can re-attach after a crash (tests/test_fault.py
drives crash → restore → identical result stream). Restore matches lanes
by query name and adjacency rows by label name, so a restoring service
whose group has a different churn history (other bucketed-Q/K/label/slot
padding) OR a different executor (mesh-written → local-restored and vice
versa) re-pads the checkpoint onto its own capacities and placement. A
query that fell back to the reference RSPQ checkpoints as a reference
engine; a service restoring such a snapshot must register it with
``engine="reference"`` (the mismatch raises otherwise).
"""
from __future__ import annotations

import collections
import dataclasses
from typing import Deque, Dict, List, Optional, Set, Tuple, Union

import jax

from .. import telemetry
from ..core.automaton import compile_query
from ..core.backend import resolve_backend
from ..core.engine import BatchedDenseRPQEngine, PendingResults, RegisteredQuery
from ..core.executor import (
    ADJ_LAYOUTS,
    DIST_LAYOUTS,
    FRONTIER_MODES,
    Executor,
    LocalExecutor,
    _next_pow2,
)
from ..core.reference import RAPQ, RSPQ


@dataclasses.dataclass
class QueryStats:
    tuples: int = 0
    results: int = 0
    conflicted: bool = False


class IngestReport(Dict[str, Set[Tuple]]):
    """New result pairs per query (a plain dict, so existing callers keep
    working), with the deletion-invalidated pairs alongside in
    :attr:`invalidated` (name -> set of (x, y) pairs a negative tuple
    removed from the valid answer set), the queries switched to the
    exact reference RSPQ path in :attr:`fallbacks` (name -> reason), and —
    when the dense group runs frontier-restricted ingest — the call's
    frontier telemetry in :attr:`frontier_stats` (rows relaxed vs the
    dense-loop row equivalent, overflow-fallback count, current capacity;
    empty dict with ``frontier="off"``)."""

    def __init__(self, new: Dict[str, Set[Tuple]],
                 invalidated: Dict[str, Set[Tuple]],
                 fallbacks: Optional[Dict[str, str]] = None,
                 frontier_stats: Optional[Dict[str, object]] = None,
                 deletions: int = 0):
        super().__init__(new)
        self.invalidated: Dict[str, Set[Tuple]] = invalidated
        self.fallbacks: Dict[str, str] = dict(fallbacks or {})
        self.frontier_stats: Dict[str, object] = dict(frontier_stats or {})
        #: negative tuples the dense group processed during this call
        #: (frontier delete telemetry — cone dispatches / fallbacks — rides
        #: in :attr:`frontier_stats` under ``delete_dispatches`` /
        #: ``delete_fallbacks``).
        self.deletions: int = int(deletions)


class RSPQFallback:
    """Exact simple-path engine for a query evicted from the dense group
    after a conflict: the paper-faithful :class:`RSPQ` plus the live-edge
    bookkeeping the dense group used to provide.

    The wrapper keeps its own (u, v, label) -> ts map of window-live edges
    so explicit deletions work even though the paper's RSPQ listing has no
    Delete algorithm: a negative tuple rebuilds a fresh RSPQ from the
    retained edges (the paper's uniform re-derivation machinery, pointer
    form). ``results`` stays monotone across rebuilds — the emitted history
    (including the dense lane's pre-switch results, which may over-report;
    that is what the conflict flag marked) is carried forward, and
    :meth:`insert` returns only pairs NEW to it."""

    def __init__(self, dfa, window: float, emitted: Optional[Set[Tuple]] = None):
        self.dfa = dfa
        self.window = float(window)
        self._edges: Dict[Tuple, float] = {}
        self._rspq = RSPQ(dfa, window)
        self._emitted: Set[Tuple] = set(emitted or ())

    @property
    def results(self) -> Set[Tuple]:
        return self._emitted | self._rspq.results

    @property
    def conflicts_detected(self) -> int:
        return self._rspq.conflicts_detected

    def seed(self, edges, now: float) -> None:
        """Replay the dense group's retained edges and sync the clock, so
        the engine answers over the current window from its first event."""
        for (u, v, label, ts) in edges:
            self._edges[(u, v, label)] = ts
            self._rspq.insert(u, v, label, ts)
        if now > float("-inf"):
            self._rspq.expire(now)

    def insert(self, u, v, label: str, ts: float) -> Set[Tuple]:
        self._edges[(u, v, label)] = ts
        before = self.results
        self._rspq.insert(u, v, label, ts)
        return self.results - before

    def delete(self, u, v, label: str, ts: float) -> Set[Tuple]:
        self._edges.pop((u, v, label), None)
        now = max(self._rspq.now, ts)
        # advance the clock BEFORE snapshotting validity, mirroring the
        # dense engine's _delete (valid_before at the event's `now`):
        # otherwise pairs the deletion event's own clock advance expired
        # would be misreported as invalidated by the negative tuple
        self._rspq.expire(now)
        before_valid = self._rspq.current_results()
        self._emitted |= self._rspq.results
        fresh = RSPQ(self.dfa, self.window)
        low = now - self.window
        for (eu, ev, el), ets in sorted(self._edges.items(), key=lambda kv: kv[1]):
            if ets > low:
                fresh.insert(eu, ev, el, ets)
        fresh.expire(now)
        self._rspq = fresh
        return before_valid - fresh.current_results()

    def expire(self, tau: Optional[float] = None) -> None:
        self._rspq.expire(tau)
        if tau is not None:
            low = tau - self.window
            self._edges = {k: t for k, t in self._edges.items() if t > low}

    def current_results(self) -> Set[Tuple]:
        return self._rspq.current_results()


class PersistentQueryService:
    def __init__(self, window: float, slide: float,
                 executor: Union[str, Executor] = "local",
                 async_decode: bool = False,
                 async_depth: int = 1,
                 rspq_fallback: bool = True,
                 adaptive_batch: bool = False,
                 max_batch: int = 32,
                 frontier: str = "off",
                 frontier_cap: int = 32,
                 adj_layout: str = "dense",
                 ell_cap: int = 8,
                 dist_layout: str = "dense",
                 dist_cap: int = 16):
        self.window = float(window)
        self.slide = float(slide)
        self._executor_spec = executor
        # frontier-restricted ingest (PR 5): "off" = dense dispatch only,
        # "on" = frontier at a fixed capacity, "auto" = frontier whose
        # capacity grows ×2 on observed overflow fallbacks. Results are
        # bit-identical in every mode (overflow falls back to the dense
        # loop IN-DISPATCH); the knob only moves per-event cost between
        # O(J·N³) and O(J·F·N²). Per-interval telemetry lands in
        # :attr:`frontier_log` and each ingest's delta in
        # ``IngestReport.frontier_stats``.
        if frontier not in FRONTIER_MODES:
            raise ValueError(
                f"unknown frontier mode {frontier!r} "
                f"({' | '.join(FRONTIER_MODES)})")
        self._frontier = frontier
        self._frontier_cap = int(frontier_cap)
        # adjacency representation (tentpole of the blocked-sparse PR):
        # "dense" = the (L, N, N) slab, "ell" = padded ELL rows + spill
        # ring (core/sparse_adj.py). Results are bit-identical; memory is
        # ∝ live edges and the seed term drops from O(N²K) to
        # O(F·d_max·K) under ELL. Per-interval occupancy telemetry lands
        # in :attr:`adjacency_log`.
        if adj_layout not in ADJ_LAYOUTS:
            raise ValueError(
                f"unknown adj_layout {adj_layout!r} "
                f"({' | '.join(ADJ_LAYOUTS)})")
        self._adj_layout = adj_layout
        self._ell_cap = int(ell_cap)
        # dist representation (tentpole of the sparse-dist PR): "dense" =
        # the (Q, N, N, K) slab, "row_sparse" = per-source-row reachable
        # sets + bounded overflow table (core/sparse_dist.py). Result
        # streams are identical in every mode; memory is ∝ reachable
        # entries and the emit scan drops from O(Q·N²·K) to
        # O(Q·N·dist_cap). Per-interval occupancy telemetry lands in
        # :attr:`dist_log`.
        if dist_layout not in DIST_LAYOUTS:
            raise ValueError(
                f"unknown dist_layout {dist_layout!r} "
                f"({' | '.join(DIST_LAYOUTS)})")
        self._dist_layout = dist_layout
        self._dist_cap = int(dist_cap)
        #: (tuples_seen_so_far, adjacency_stats snapshot) history, one
        #: entry per slide boundary when the layout is "ell"
        self.adjacency_log: List[Tuple[int, Dict[str, object]]] = []
        #: (tuples_seen_so_far, dist_stats snapshot) history, one entry
        #: per slide boundary when the dist layout is "row_sparse"
        self.dist_log: List[Tuple[int, Dict[str, object]]] = []
        #: (tuples_seen_so_far, per-interval frontier stats delta) history
        self.frontier_log: List[Tuple[int, Dict[str, object]]] = []
        self._frontier_mark: Optional[Dict[str, object]] = None
        self._async_decode = bool(async_decode)
        # bounded deferred-decode FIFO: up to `async_depth` dispatches may
        # be in flight before the oldest emit frontier is pulled off the
        # device (async_decode=True, depth 1 = the PR 3 single-handle
        # behavior). Handles resolve in dispatch order — the engine's
        # monotone per-query result sets require FIFO decode — and each
        # snapshots the interner at dispatch, so slot recycling between
        # dispatch and resolve cannot remap decoded pairs.
        self._async_depth = max(1, int(async_depth))
        self._rspq_fallback = bool(rspq_fallback)
        # adaptive micro-batching (opt-in): grow/shrink the dense group's
        # batch_size in x2 steps from the executor's skip counters — see
        # ingest(). B > 1 trades the documented batch-boundary skew for
        # fewer dispatches, so it is never on by default.
        self._adaptive_batch = bool(adaptive_batch)
        self._max_batch = max(1, int(max_batch))
        self._adapt_marks: Optional[Tuple[int, int]] = None
        #: (tuples_seen_so_far, chosen_size) history of adaptive decisions
        self.batch_size_log: List[Tuple[int, int]] = []
        # reference (pointer) engines, one per query
        self._ref_engines: Dict[str, object] = {}
        # dense queries: name -> registration kwargs; grouped lazily until
        # first ingest, then the group is LIVE and mutated in place
        self._dense_specs: Dict[str, Dict] = {}
        self._group: Optional[BatchedDenseRPQEngine] = None
        self._ingest_started = False
        self.stats: Dict[str, QueryStats] = {}
        self._next_expiry = slide

    def _make_executor(self, backend) -> Executor:
        if isinstance(self._executor_spec, Executor):
            return self._executor_spec
        if self._executor_spec == "mesh":
            from ..distributed.executor import MeshExecutor

            return MeshExecutor(backend=backend, frontier=self._frontier,
                                frontier_cap=self._frontier_cap,
                                adj_layout=self._adj_layout,
                                ell_cap=self._ell_cap,
                                dist_layout=self._dist_layout,
                                dist_cap=self._dist_cap)
        if self._executor_spec == "local":
            return LocalExecutor(backend, frontier=self._frontier,
                                 frontier_cap=self._frontier_cap,
                                 adj_layout=self._adj_layout,
                                 ell_cap=self._ell_cap,
                                 dist_layout=self._dist_layout,
                                 dist_cap=self._dist_cap)
        raise ValueError(
            f"unknown executor {self._executor_spec!r} (local | mesh | instance)")

    @staticmethod
    def _stats_delta(cur: Dict[str, object],
                     prev: Dict[str, object]) -> Dict[str, object]:
        """Difference two frontier-stat snapshots: counters subtract,
        level values (mode, cap, max_lane_rows) pass through, occupancy is
        recomputed over the interval's own rows."""
        level_keys = ("mode", "cap", "max_lane_rows")
        delta = {
            k: (cur[k] - prev.get(k, 0)
                if isinstance(cur[k], int) and k not in level_keys
                else cur[k])
            for k in cur
        }
        dr = delta.get("dense_row_equiv", 0)
        # An interval with zero dense-row-equivalent work carries no
        # occupancy signal at all (no dispatch touched any rows) — report
        # None rather than 0.0 so consumers (adaptive batching) can tell
        # "idle" apart from "genuinely sparse frontiers".
        delta["occupancy"] = (delta.get("rows_relaxed", 0) / dr) if dr else None
        return delta

    @staticmethod
    def _frontier_healthy(finterval: Dict[str, object]) -> bool:
        """True when the interval's frontier telemetry shows cheap, live
        dispatches: some dispatches ran, their measured row occupancy is
        tiny, and none overflowed to the dense loop. An interval with no
        signal — no dispatches at all, or ``occupancy is None`` because
        zero dense-row-equivalent work happened — is NOT healthy: it says
        nothing about the frontier, and treating it as healthy would hold
        the batch size frozen across idle slides."""
        if not finterval or not finterval.get("dispatches", 0):
            return False
        occ = finterval.get("occupancy")
        if occ is None:
            return False
        return occ < 0.05 and not finterval.get("fallbacks", 0)

    def _frontier_delta(self) -> Dict[str, object]:
        """Frontier-stat delta since the last mark (per-interval telemetry;
        empty when the frontier is off or no dense group exists)."""
        if self._group is None or self._frontier == "off":
            return {}
        cur = self._group.executor.frontier_stats
        delta = self._stats_delta(cur, self._frontier_mark or {})
        self._frontier_mark = cur
        return delta

    @property
    def queries(self) -> Dict[str, object]:
        """name -> engine handling it (the batched group for dense queries)."""
        self._ensure_group()
        out: Dict[str, object] = dict(self._ref_engines)
        for name in self._dense_specs:
            out[name] = self._group
        return out

    def register(
        self,
        name: str,
        expr: str,
        engine: str = "dense",            # dense | reference
        path_semantics: str = "arbitrary",  # arbitrary | simple
        n_slots: int = 256,
        batch_size: int = 1,
        backend: str = "jnp",
    ) -> Set[Tuple]:
        """Register a persistent query; works before AND after ingestion has
        started. A dense registration into a live group re-pads device state
        in place and seeds the query over the retained graph; its INITIAL
        result pairs (valid over the current window) are returned — for all
        other paths the returned set is empty.

        Caveat: the FIRST dense query registered after ingestion has started
        cannot be seeded (no dense group retained the graph; prefix content
        seen only by reference engines is not recoverable) — its group is
        materialized empty at registration and answers from this point of
        the stream on."""
        if name in self.stats and (name in self._dense_specs
                                   or name in self._ref_engines):
            raise ValueError(f"query {name!r} already registered")
        if engine == "dense":
            # validate NOW, with the known-backend list ("palas" used to run
            # the jnp oracle without a whisper); resolving also interns
            # string names so the group's backend set dedupes by identity
            backend = resolve_backend(backend)
        dfa = compile_query(expr)
        initial: Set[Tuple] = set()
        if engine == "dense":
            if self._group is not None and self._ingest_started:
                # LIVE registration: the group's device state is re-padded
                # in place; capacity kwargs (n_slots, batch_size, backend)
                # all adopt the group's existing values
                initial = self._group.register_query(
                    RegisteredQuery(name, dfa, self.window, path_semantics)
                )
                self._dense_specs[name] = dict(
                    dfa=dfa, path_semantics=path_semantics,
                    n_slots=self._group.n_slots,
                    batch_size=self._group.batch_size,
                    backend=self._group.backend,
                )
            else:
                self._dense_specs[name] = dict(
                    dfa=dfa, path_semantics=path_semantics, n_slots=n_slots,
                    batch_size=batch_size, backend=backend,
                )
                self._group = None  # rebuilt (empty) at next ingest/snapshot
                if self._ingest_started:
                    # FIRST dense query arriving mid-stream: no dense group
                    # retained the graph, so there is nothing to seed from —
                    # materialize the (empty) group NOW so the query starts
                    # tracking the stream from this point on, rather than
                    # silently deferring to the next ingest. Queries joining
                    # an EXISTING group are seeded over the retained window
                    # (the branch above); prefix content seen only by
                    # reference engines is not recoverable.
                    self._ensure_group()
        elif path_semantics == "simple":
            self._ref_engines[name] = RSPQ(dfa, self.window)
        else:
            self._ref_engines[name] = RAPQ(dfa, self.window)
        if name not in self.stats:  # a reused name keeps its history
            self.stats[name] = QueryStats()
        return initial

    def deregister(self, name: str) -> None:
        """Retire a persistent query mid-stream. Dense: the group lane
        becomes inert padding (reclaimed by the next registration); the
        remaining queries' result streams are unaffected. The stats entry is
        kept as history."""
        if name in self._dense_specs:
            del self._dense_specs[name]
            if self._group is not None:
                if self._ingest_started:
                    self._group.deregister_query(name)
                else:
                    self._group = None  # rebuilt without it at next ingest
        elif name in self._ref_engines:
            del self._ref_engines[name]
        else:
            raise KeyError(f"no registered query named {name!r}")

    def _ensure_group(self) -> None:
        if self._group is not None or not self._dense_specs:
            return
        backends = {s["backend"] for s in self._dense_specs.values()}
        if len(backends) > 1:
            raise ValueError(f"dense queries must share one backend, got {backends}")
        backend = backends.pop()
        specs = [
            RegisteredQuery(name, s["dfa"], self.window, s["path_semantics"])
            for name, s in self._dense_specs.items()
        ]
        self._group = BatchedDenseRPQEngine(
            specs,
            n_slots=max(s["n_slots"] for s in self._dense_specs.values()),
            # exactness dominates: the smallest requested micro-batch bounds
            # the group's batch-boundary skew for every member query
            batch_size=min(s["batch_size"] for s in self._dense_specs.values()),
            backend=backend,
            executor=self._make_executor(backend),
        )

    def _maybe_fallback(self, fallbacks: Dict[str, str], resolve_cb) -> None:
        """Route conflicted simple-path dense lanes to the exact reference
        RSPQ engine (seeded from the retained graph); record the switch."""
        if not self._rspq_fallback or self._group is None:
            return
        for qi, spec in list(self._group.live_items()):
            if spec.path_semantics != "simple":
                continue
            if not self._group.per_query_conflicted[qi]:
                continue
            resolve_cb()  # settle deferred decodes before mutating lanes
            name = spec.name
            fb = RSPQFallback(spec.dfa, spec.window,
                              emitted=self._group.per_query_results[qi])
            fb.seed(self._group.retained_edges(), self._group._host_now)
            self._group.deregister_query(name)
            del self._dense_specs[name]
            self._ref_engines[name] = fb
            fallbacks[name] = "conflict -> reference RSPQ"
            if name in self.stats:
                self.stats[name].conflicted = True

    def ingest(self, stream) -> IngestReport:
        """Feed the whole stream; returns an :class:`IngestReport`: the new
        result pairs per query (dict interface), with the pairs invalidated
        by explicit deletions alongside in ``.invalidated`` and any
        dense→RSPQ switches in ``.fallbacks``.

        With ``adaptive_batch=True`` (opt-in) dense inserts are buffered
        into micro-batches whose size the service steers from the
        executor's skip counters: at each slide boundary it reads the
        interval's ``query_rounds_total`` vs ``unmasked_query_rounds_total``
        delta — a large no-op relaxation tail means most of each dispatch's
        work is already-converged lanes riding along, so per-event dispatch
        overhead dominates useful work and the micro-batch DOUBLES (up to
        ``max_batch``); a small tail means the lanes genuinely relax every
        round and the batch HALVES back toward the exact per-tuple regime
        (B is always a power-of-two multiple of 1, so the bucketed jit
        cache sees few distinct shapes). Decisions land in
        :attr:`batch_size_log`; B > 1 carries the engine's documented
        batch-boundary skew, which is why this is never on by default.
        """
        with telemetry.span("service.ingest") as span:
            self._ensure_group()
            self._ingest_started = True
            new_results: Dict[str, Set[Tuple]] = {name: set() for name in self.stats}
            invalidated: Dict[str, Set[Tuple]] = {name: set() for name in self.stats}
            fallbacks: Dict[str, str] = {}
            # reading frontier_stats flushes the executor's queued counters —
            # but the PREVIOUS call's end-of-ingest read already drained them,
            # so this start-of-call snapshot is amortized-free (it only pays
            # when the engine was driven directly between service calls); the
            # per-call cost is bounded by flushing this call's own dispatches,
            # which reporting per-call stats requires anyway
            call_mark: Dict[str, object] = (
                dict(self._group.executor.frontier_stats)
                if self._group is not None and self._frontier != "off" else {})
            # bounded FIFO (async_depth) — deque so the drain below is O(1)
            # per handle instead of list.pop(0)'s O(n) shift
            pending: Deque[PendingResults] = collections.deque()
            dense_buf: List = []               # adaptive micro-batch buffer
            del_buf: List = []                 # negative-tuple micro-batch buffer
            deletions = [0]                    # negative tuples seen by the group

            def resolve_pending(limit: int = 0) -> None:
                """Resolve outstanding decode handles down to `limit` (dispatch
                order; each handle snapshotted the interner at dispatch)."""
                while len(pending) > limit:
                    fresh = pending.popleft().resolve()
                    for qi, spec in self._group.live_items():
                        new_results[spec.name] |= fresh[qi]

            def flush_dense() -> None:
                """Dispatch the buffered dense inserts as one micro-batch."""
                if not dense_buf:
                    return
                batch = [(s.src, s.dst, s.label, s.ts) for s in dense_buf]
                handle = self._group.insert_batch_pending(batch)
                pending.append(handle)
                # pull results down to the in-flight budget: depth k means the
                # device->host transfer of dispatch i overlaps dispatches
                # i+1..i+k instead of blocking the hot path
                resolve_pending(self._async_depth if self._async_decode else 0)
                for qi, spec in self._group.live_items():
                    self.stats[spec.name].tuples += len(batch)
                dense_buf.clear()
                self._maybe_fallback(fallbacks, lambda: resolve_pending(0))

            def flush_deletes() -> None:
                """Dispatch the buffered negative tuples as one micro-batch
                through the engine's chunked delete path (frontier cone per
                chunk when the frontier is on). Only one of dense_buf/del_buf
                is ever non-empty — the event loop flushes the other before
                buffering — so stream order is preserved."""
                if not del_buf:
                    return
                resolve_pending()
                batch = [(s.src, s.dst, s.label, s.ts) for s in del_buf]
                inv = self._group.delete_batch(batch)
                for qi, spec in self._group.live_items():
                    self.stats[spec.name].tuples += len(batch)
                    invalidated[spec.name] |= inv[qi]
                deletions[0] += len(batch)
                del_buf.clear()
                self._maybe_fallback(fallbacks, lambda: resolve_pending(0))

            def mark_interval() -> Dict[str, object]:
                """Per-interval frontier telemetry: append the delta since the
                last slide boundary to :attr:`frontier_log` and hand it to the
                batch steering below."""
                delta = self._frontier_delta()
                seen = max((self.stats[s.name].tuples
                            for _qi, s in self._group.live_items()),
                           default=0) if self._group is not None else 0
                if delta:
                    self.frontier_log.append((seen, delta))
                if (self._group is not None
                        and self._group.executor.adj_layout == "ell"):
                    self.adjacency_log.append(
                        (seen, self._group.executor.adjacency_stats))
                if (self._group is not None
                        and self._group.executor.dist_layout == "row_sparse"):
                    self.dist_log.append(
                        (seen, self._group.executor.dist_stats))
                return delta

            def adapt_batch(finterval: Dict[str, object]) -> None:
                """Steer the dense micro-batch size from the interval's no-op
                relaxation tail AND the frontier telemetry (see docstring)."""
                if not self._adaptive_batch or self._group is None:
                    return
                ex = self._group.executor
                qr, uqr = ex.query_rounds_total, ex.unmasked_query_rounds_total
                if self._adapt_marks is not None:
                    dqr = qr - self._adapt_marks[0]
                    duqr = uqr - self._adapt_marks[1]
                    if duqr > 0:
                        noop_frac = 1.0 - dqr / duqr
                        b = self._group.batch_size
                        # the no-op tail argues for a bigger B (dispatch
                        # overhead dominates useful work) — but when the
                        # frontier is live and healthy (tiny row occupancy, no
                        # overflow pressure) each dispatch is ALREADY cheap in
                        # proportion to its dirty rows, so growing B would
                        # trade exactness (batch-boundary skew) for little:
                        # hold B instead
                        frontier_healthy = self._frontier_healthy(finterval)
                        if noop_frac >= 0.3 and b < self._max_batch \
                                and not frontier_healthy:
                            b *= 2
                        elif noop_frac < 0.1 and b > 1:
                            b //= 2
                        if b != self._group.batch_size:
                            self._group.batch_size = b
                            seen = max((self.stats[s.name].tuples
                                        for _qi, s in self._group.live_items()),
                                       default=0)
                            self.batch_size_log.append((seen, b))
                self._adapt_marks = (qr, uqr)

            n_sgts = 0
            for sgt in stream:
                n_sgts += 1
                # lazy expiration at slide boundaries (eager evaluation)
                if sgt.ts >= self._next_expiry:
                    flush_dense()
                    flush_deletes()
                    resolve_pending()
                    with telemetry.span("service.expire"):
                        if self._group is not None:
                            self._group.expire(sgt.ts)
                        for eng in self._ref_engines.values():
                            eng.expire(sgt.ts)
                        while self._next_expiry <= sgt.ts:
                            self._next_expiry += self.slide
                        adapt_batch(mark_interval())
                # snapshot BEFORE the dense step: a fallback fired by this very
                # event must not re-feed the event to its new reference engine
                refs_this_event = list(self._ref_engines.items())
                if self._group is not None:
                    if sgt.op == "+":
                        flush_deletes()
                        dense_buf.append(sgt)
                        if (not self._adaptive_batch
                                or len(dense_buf) >= self._group.batch_size):
                            flush_dense()
                    else:
                        flush_dense()
                        del_buf.append(sgt)
                        if (not self._adaptive_batch
                                or len(del_buf) >= self._group.batch_size):
                            flush_deletes()
                for name, eng in refs_this_event:
                    if sgt.op == "+":
                        res = eng.insert(sgt.src, sgt.dst, sgt.label, sgt.ts)
                        new_results[name] |= res
                    else:
                        # expire to the deletion's own clock first, as the
                        # dense delete judges validity at it: a pair the
                        # window already dropped is not invalidated by the
                        # negative tuple (lazy expiry would report it here)
                        eng.expire(sgt.ts)
                        inv = eng.delete(sgt.src, sgt.dst, sgt.label, sgt.ts)
                        if inv:
                            invalidated[name] |= set(inv)
                    self.stats[name].tuples += 1
            flush_dense()
            flush_deletes()
            resolve_pending()
            for name in self.stats:
                st = self.stats[name]
                if name in self._dense_specs or name in self._ref_engines:
                    st.results = len(self.results(name))
                    st.conflicted = st.conflicted or self._conflicted(name)
            fstats: Dict[str, object] = {}
            if call_mark and self._group is not None:
                fstats = self._stats_delta(
                    self._group.executor.frontier_stats, call_mark)
            span.value = n_sgts
            return IngestReport(new_results, invalidated, fallbacks, fstats,
                                deletions=deletions[0])

    def results(self, name: str) -> Set[Tuple]:
        if name in self._dense_specs:
            self._ensure_group()
            return set(self._group.per_query_results[self._group.lane_of(name)])
        return set(self._ref_engines[name].results)

    def _conflicted(self, name: str) -> bool:
        if name in self._dense_specs and self._group is not None:
            return bool(self._group.per_query_conflicted[self._group.lane_of(name)])
        eng = self._ref_engines.get(name)
        return bool(getattr(eng, "conflicts_detected", 0)) if eng else False

    # -- state persistence ----------------------------------------------------

    def snapshot(self, directory: str, step: int, *,
                 wal_lsn: Optional[int] = None,
                 extra_meta: Optional[Dict[str, object]] = None,
                 async_save: bool = False,
                 _crash_after: Optional[str] = None) -> None:
        """Checkpoint the whole service. ``wal_lsn`` records the
        write-ahead-log position this snapshot covers (the supervisor's
        recovery replays only records past it); ``async_save=True`` defers
        the file IO to a background thread (``ckpt.async_save`` — the
        device→host transfer still happens here, so the state is
        consistent no matter what the stream does next); ``_crash_after``
        is the chaos harness's mid-save kill switch (ckpt.save stages).

        The dense group's deferred-decode FIFO is drained FIRST: an
        in-flight async-decode batch (``async_depth>1``) has already
        mutated device state, so saving before its results land in
        ``per_query_results`` would snapshot an emitted mask ahead of the
        recorded results — restore + replay would then drop those pairs
        (the device diff thinks they were already reported). Draining
        makes snapshot a sequence point: state and results agree."""
        from ..checkpoint import ckpt

        with telemetry.span("checkpoint.capture") as span:
            self._ensure_group()
            if self._group is not None:
                # belt-and-braces with engine.state_arrays()/results_state()
                # (each drains too): ONE sequence point, visible at the
                # service boundary, regression-pinned in tests/test_fault.py
                self._group._drain_pending()
            state: Dict[str, object] = {}
            extra: Dict[str, object] = {
                "step": step,
                "next_expiry": self._next_expiry,
                "reference": sorted(self._ref_engines),
            }
            if wal_lsn is not None:
                extra["wal_lsn"] = int(wal_lsn)
            if extra_meta:
                # caller metadata (e.g. the supervisor's churn catalog) rides
                # the manifest; reserved keys stay ours
                for k, v in extra_meta.items():
                    extra.setdefault(k, v)
            if self._group is not None:
                state["dense_group"] = self._group.state_arrays()
                extra["dense"] = {
                    # the LIVE query set, lane by lane (None = inert padding):
                    # restore matches lanes by name, so the restoring group may
                    # have a different bucketed-Q layout (or executor shard
                    # quantum)
                    "order": [s.name if s is not None else None
                              for s in self._group.lane_specs],
                    "labels": list(self._group.labels),
                    "interner": self._group.interner_state(),
                    # learned capacity occupancy (all ×2-bucketed): a restored
                    # service starts at these instead of re-learning them from
                    # overflow pressure — frontier_cap from "auto" growth,
                    # dist_cap from row-sparse drains, ell_cap from adjacency
                    # packs; harmless no-ops for layouts/modes that are off
                    "capacities": {
                        "frontier_cap": int(self._group.executor.frontier_cap),
                        "ell_cap": int(self._group.executor.ell_cap),
                        "dist_cap": int(self._group.executor.dist_cap),
                        "dist_ovf_cap": (
                            int(self._group.executor.dist_ovf_cap)
                            if self._group.executor.dist_ovf_cap is not None
                            else None),
                    },
                    **self._group.results_state(),
                }
            for name, eng in self._ref_engines.items():
                state[f"refeng.{name}"] = ckpt.pickle_leaf(eng)
            span.value = sum(int(x.nbytes) for x in jax.tree.leaves(state))
            if async_save:
                ckpt.async_save(directory, step, state, extra=extra,
                                _crash_after=_crash_after)
            else:
                # an async save still writing to `directory` may target the
                # same step dir: join it first so the two never interleave
                ckpt.wait_pending(directory)
                ckpt.save(directory, step, state, extra=extra,
                          _crash_after=_crash_after)

    def restore(self, directory: str) -> int:
        from ..checkpoint import ckpt

        self._ensure_group()
        like: Dict[str, object] = {}
        if self._group is not None:
            like["dense_group"] = self._group.state_arrays()
        for name in self._ref_engines:
            like[f"refeng.{name}"] = ckpt.pickle_like()
        state, extra = ckpt.restore(directory, like=like)
        if self._group is not None:
            meta = extra["dense"]
            # adopt the snapshot's LEARNED capacities first (never shrink —
            # max with our own), so the re-placement below packs at the
            # occupancy the crashed service had already learned instead of
            # re-discovering it through overflow pressure
            caps = meta.get("capacities", {})
            ex = self._group.executor
            # saved caps are already ×2-bucketed; _next_pow2 is identity on
            # them and keeps manifest tampering from un-bucketing the jits
            if caps.get("frontier_cap"):
                ex.frontier_cap = max(
                    ex.frontier_cap, _next_pow2(int(caps["frontier_cap"])))
            if caps.get("ell_cap"):
                ex.ell_cap = max(ex.ell_cap, _next_pow2(int(caps["ell_cap"])))
            if caps.get("dist_cap"):
                ex.dist_cap = max(ex.dist_cap,
                                  _next_pow2(int(caps["dist_cap"])))
            if caps.get("dist_ovf_cap"):
                prev = ex.dist_ovf_cap if ex.dist_ovf_cap is not None else 1
                ex.dist_ovf_cap = max(
                    prev, _next_pow2(int(caps["dist_ovf_cap"])))
            # lane-by-name adoption: tolerant of bucketed-Q/K/label/slot
            # padding differences AND executor changes (mesh <-> local);
            # raises if the LIVE query sets differ
            self._group.adopt_state(
                state["dense_group"],
                meta["order"],
                meta.get("labels", list(self._group.labels)),
            )
            self._group.load_interner(meta["interner"])
            self._group.load_results_state(meta)
        for name in self._ref_engines:
            self._ref_engines[name] = ckpt.unpickle_leaf(state[f"refeng.{name}"])
        self._next_expiry = float(extra.get("next_expiry", self.slide))
        self._ingest_started = True
        return int(extra["step"])
