"""Dense TPU-native streaming RPQ engine (the paper's technique, tensorized),
multi-query batched: Q persistent queries share ONE adjacency and step as one
jitted program, and the query set is LIVE — queries register and deregister
while the stream keeps flowing (the paper's persistent-query execution
model, §2).

Layering (PR 3): the engine is pure ORCHESTRATION — vertex interning, query
lifecycle, result decoding, checkpoint metadata. Everything device-facing
(state arrays, jitted dispatches, round accounting) lives behind the
executor interface (:mod:`repro.core.executor`):

    stream -> service -> engine -> executor -> semiring rounds -> kernels

Two executors plug in: :class:`~repro.core.executor.LocalExecutor` (the
single-device path, bit-identical to the pre-refactor engine) and
:class:`~repro.distributed.executor.MeshExecutor` (Q lanes sharded over a
device mesh with convergence-aware per-shard dispatch — converged/inert
lanes finally SKIP their contraction work instead of being accounted and
zeroed). Result streams are identical across executors (asserted by
tests/test_executor.py and benchmarks/fig14_sharded_engine.py).

State (all fixed-capacity, jit-static shapes between lifecycle events;
capacities GROW at runtime — Q/K/label since PR 2, the vertex axis since
this PR):
    adj     (L, N, N)    f32   newest edge timestamp per (label, u, v); -inf
                               none. L = |union alphabet| of ALL registered
                               queries — the stream is ingested ONCE, not
                               re-ingested per query.
    dist    (Q, N, N, K) f32   per-query bottleneck closure D[q, x, v, s]
                               (DESIGN.md §2); K padded to max_q k_q, the
                               padding states are inert (never scattered
                               into, finals masks padded False).
    emitted (Q, N, N)    bool  pairs already reported per query
                               (implicit-window monotone)
    now     ()           f32   latest event time seen (shared stream clock;
                               EVERY event timestamp advances it, including
                               tuples outside the union alphabet)

The per-query DFA transition tables are flattened into one global list
(semiring.BatchedTransitionTable): a relaxation round is a single
gather → batched max-min contraction → segment-max scatter, so `ingest →
relax → emit` for all Q queries is ONE dispatch per micro-batch instead of
Q. Per-query windows are a (Q,) vector applied as read-time thresholds.

Query lifecycle (beyond-paper, PR 2): the Q axis is a set of LANES.
:meth:`register_query` works at any point of the stream — it re-pads device
state in place (Q grows in buckets, K to the new ``max_q k_q``, the label
axis when the union alphabet expands; all growth is append-only so existing
state keeps its indices and the jit cache is reused within a bucket), then
seeds the new lane with one closure pass over the EXISTING shared
adjacency, so the query immediately answers over the live window (its
initial valid pairs are returned and count as emitted).
:meth:`deregister_query` clears the lane to inert padding; the next
registration reclaims it. Capacities never shrink. Lane capacity is rounded
to the executor's ``q_multiple`` (1 locally; the lane-shard count on a
mesh) so inert padding lands on whole shards the convergence mask skips.

Vertex capacity (beyond-paper, this PR): ``n_slots`` grows on demand — when
the interner runs out of live slots even after compaction, the vertex axes
re-pad append-only (doubling, rounded to the executor's ``n_multiple``)
instead of raising. Checkpoints restore across differing vertex capacities
(the smaller side is padded; a larger checkpoint grows the engine first).

Per-query convergence masking: the closure masks each query out of the
relaxation as soon as its own round produces no change (sound: a transition
only ever reads its owning query's slices), so a converged query's lane
settles at ITS OWN fixpoint. On the dense single-device path the round is
shape-static — the mask buys exact accounting (executor counters
``query_rounds_total`` vs ``unmasked_query_rounds_total``) — while the mesh
executor turns the same mask into skipped contractions per lane shard.

Frontier-restricted ingest (beyond-paper, PR 5): with ``frontier="on" |
"auto"`` the executor's ingest dispatch relaxes only the source rows the
micro-batch dirties (seeded in-dispatch from the batch's source slots —
the engine already threads them through ``ingest_batch``), so per-event
cost is O(J·F·N²) instead of O(J·N³); overflow falls back to the dense
loop inside the dispatch, so results are bit-identical in every mode.
Explicit deletions ride the same machinery since PR 6: the deleted edge's
cone (the rows whose derivations can pass through it, computed on the
pre-delete state) is cleared and re-derived at frontier prices instead of
resetting every row, and :meth:`delete_batch` chunks negative tuples
through the micro-batch path exactly like inserts. Lane-seeding closures
(:meth:`register_query`) and checkpoint adoption stay on the dense
closure — each is a from-scratch re-derivation that dirties every row by
construction — and compaction needs no frontier bookkeeping because no
frontier state persists across dispatches (the dirty set is recomputed
per dispatch, so slot recycling and vertex-axis growth cannot invalidate
stale row indices).

Key property of the (max, min) formulation (beyond-paper, §Perf): *window
expiry needs no index maintenance* — a pair is valid iff its bottleneck
timestamp exceeds ``now - |W_q|``, so expiry is a threshold at read time.
The paper's ExpiryRAPQ machinery is only needed for (a) explicit deletions
(closure re-computation, the paper's own uniform machinery) and (b) vertex
slot recycling (python-side compaction, thresholded at the LARGEST window
of the group so no query loses live state; with no live queries the last
retention threshold is kept so the shared graph survives an empty interval
of the query set).

Semantics vs the paper (B = micro-batch size, Q = #queries):
  * B = 1: the per-query result streams match the paper tuple-for-tuple for
    every query in the group (tested) — a tuple outside query q's alphabet
    steps q's closure with an unchanged adjacency, a no-op.
  * B > 1: results are evaluated at batch boundaries (documented skew: a
    path valid only strictly inside a batch interval is not reported).
    Additionally, with Q > 1 the batch PACKING differs from Q independent
    engines: independent engines drop out-of-alphabet tuples before filling
    a batch, while the group packs every tuple in the union alphabet — so
    batch boundaries (and hence which intra-batch paths are observable)
    can differ per query from a solo run of that query. B = 1 has no skew.
  * implicit windows, eager evaluation, lazy expiration — as in the paper.
  * a query registered mid-stream answers over the CURRENT window content
    from its first instant: its result stream is identical to a freshly
    built group fed the retained graph and then the tail of the stream
    (benchmarks/fig13_query_churn.py asserts this).
"""
from __future__ import annotations

import collections
import math
from typing import (Deque, Dict, List, NamedTuple, Optional, Sequence, Set,
                    Tuple)

import jax
import jax.numpy as jnp
import numpy as np

from .. import telemetry
from .automaton import DFA
from .executor import (
    BatchedEngineArrays,
    Executor,
    LocalExecutor,
    QueryTables,
    init_batched_arrays,
)
from .semiring import NEG_INF, BatchedTransitionTable, TransitionTable

Pair = Tuple[object, object]

Q_BUCKET = 4        # lane-capacity growth quantum (compile-cache reuse)
LABEL_BUCKET = 4    # label-axis rounding (absorbs small alphabet growth)


def _round_up(n: int, b: int) -> int:
    return max(n + (-n) % b, b)


# a lane with no registered query: empty language, no transitions, k=1
_INERT_DFA = DFA(
    labels=(),
    delta=np.full((1, 0), -1, np.int32),
    start=0,
    finals=frozenset(),
)


class EngineArrays(NamedTuple):
    """Single-query view (legacy layout) — the Q=1 slice of the batched
    state, kept as the public surface of :class:`DenseRPQEngine` so sharded
    deployments can re-place individual leaves (examples/distributed_rpq)."""

    adj: jnp.ndarray      # (L, N, N) f32
    dist: jnp.ndarray     # (N, N, K) f32
    emitted: jnp.ndarray  # (N, N) bool
    now: jnp.ndarray      # () f32


def init_arrays(n_slots: int, n_labels: int, k: int) -> EngineArrays:
    b = init_batched_arrays(n_slots, n_labels, 1, k)
    return EngineArrays(b.adj, b.dist[0], b.emitted[0], b.now)


@jax.jit
def _conflict_possible(
    dist: jnp.ndarray,           # (Q, N, N, K)
    not_contained: jnp.ndarray,  # (Q, K, K), 1 where [s] !>= [t]
    low: jnp.ndarray,            # (Q,)
) -> jnp.ndarray:
    """Over-approximate RSPQ conflict detection (Definition 16), per query:
    some root reaches some vertex v in states s and t with [s] ⊉ [t].
    Ancestorship is over-approximated by co-reachability (sound: never
    misses a conflict)."""
    p = (dist > low[:, None, None, None]).astype(jnp.float32)  # (Q, N, N, K)
    m = not_contained.astype(jnp.float32)
    cnt = jnp.einsum("qxvs,qst,qxvt->q", p, m, p)
    return cnt > 0


# ---------------------------------------------------------------------------
# Python orchestration: vertex interning, query lifecycle, result decoding
# ---------------------------------------------------------------------------


class RegisteredQuery(NamedTuple):
    """One persistent query of a batched group."""

    name: str
    dfa: DFA
    window: float
    path_semantics: str = "arbitrary"  # arbitrary | simple


def _fetch_result(result_dev) -> np.ndarray:
    """A dispatch's result mask on the host: wait for the dispatch to
    finish (``engine.result_wait``), then copy the mask
    (``engine.result_copy``, valued in bytes)."""
    with telemetry.span("engine.result_wait"):
        jax.block_until_ready(result_dev)
    with telemetry.span("engine.result_copy", result_dev.nbytes):
        return np.asarray(result_dev)


class PendingResults:
    """Deferred result decoding for one :meth:`insert_batch_pending` call.

    The device->host transfer of the emit frontier happens at
    :meth:`resolve` time, so a caller (streaming/service.py's async path)
    can dispatch the NEXT micro-batch before pulling the previous one's
    results — the transfer overlaps device compute instead of blocking the
    hot path. Each chunk snapshots the vertex interner (slot recycling
    between dispatch and resolve must not remap decoded pairs). Handles
    resolve in dispatch order (FIFO through the engine) so the monotone
    per-query result sets dedup correctly; the engine drains outstanding
    handles before any lane-set mutation (register/deregister/adopt)."""

    def __init__(self, engine: "BatchedDenseRPQEngine", q_cap: int):
        self._engine = engine
        self._chunks: List[Tuple[object, List[Optional[object]], float]] = []
        self._fresh: List[Set[Pair]] = [set() for _ in range(q_cap)]
        self._decoded = False

    def _add(self, new_dev, vertex_of: List[Optional[object]], t: float) -> None:
        self._chunks.append((new_dev, vertex_of, t))

    def _decode_chunks(self) -> None:
        for new_dev, vertex_of, t in self._chunks:
            self._engine._decode_new_into(
                _fetch_result(new_dev), vertex_of, t, self._fresh)
        self._chunks.clear()
        self._decoded = True

    def resolve(self) -> List[Set[Pair]]:
        """Per-lane NEW result pairs (idempotent; forces the host sync)."""
        if not self._decoded:
            self._engine._drain_pending(upto=self)
        return self._fresh


class BatchedDenseRPQEngine:
    """Q persistent RPQs over ONE stream, stepped as one jitted program.

    All queries share the vertex interner and the (L, N, N) adjacency over
    the union label alphabet; per-query closure state is stacked along the
    leading Q axis as LANES. The lane list (``lane_specs``) may contain
    ``None`` holes — inert padding left by :meth:`deregister_query`, by
    bucketed Q growth, or by rounding to the executor's lane-shard count —
    which the next :meth:`register_query` reclaims. Per-lane accessors
    (``per_query_results``, ``current_results``, the lists returned by
    :meth:`insert_batch` / :meth:`delete`) are indexed by lane;
    :meth:`lane_of` maps a query name to its lane.

    ``executor`` selects the device path: default
    :class:`~repro.core.executor.LocalExecutor` (single device), or a
    :class:`~repro.distributed.executor.MeshExecutor` for Q-sharded
    execution with convergence-aware dispatch. The engine itself never
    touches device arrays directly.

    Per-query ``path_semantics`` follows the single-engine contract:
    "simple" (RSPQ) uses the Mendelzon–Wood tractable class and flags
    possibly-over-reporting windows in :attr:`per_query_conflicted`.
    """

    def __init__(
        self,
        queries: Sequence[RegisteredQuery],
        n_slots: int = 128,
        batch_size: int = 32,
        backend="jnp",  # name in backend.KNOWN_BACKENDS or a ContractionBackend
        executor: Optional[Executor] = None,
        frontier: str = "off",   # off | on | auto (executor ingest mode)
        frontier_cap: int = 32,
        adj_layout: str = "dense",  # dense | ell (executor adjacency layout)
        ell_cap: int = 8,
        dist_layout: str = "dense",  # dense | row_sparse (dist layout)
        dist_cap: int = 16,
    ):
        queries = list(queries)
        if not queries:
            raise ValueError("register at least one query")
        for q in queries:
            if q.dfa.containment is None:
                raise ValueError(f"compile query {q.name!r} with compile_query()")
        names = [q.name for q in queries]
        if len(set(names)) != len(names):
            raise ValueError(f"duplicate query names: {names}")
        # frontier kwargs configure the default executor only; an explicit
        # executor instance arrives already configured
        self.executor = executor if executor is not None else LocalExecutor(
            backend, frontier=frontier, frontier_cap=frontier_cap,
            adj_layout=adj_layout, ell_cap=ell_cap,
            dist_layout=dist_layout, dist_cap=dist_cap)
        self.backend = self.executor.backend
        self.lane_specs: List[Optional[RegisteredQuery]] = list(queries)
        # round lane capacity to the executor's shard quantum (inert padding
        # lanes; the convergence mask skips them wholesale)
        pad = _round_up(len(queries), self.executor.q_multiple) - len(queries)
        self.lane_specs.extend([None] * pad)
        self.n_slots = _round_up(n_slots, self.executor.n_multiple)
        self.batch_size = batch_size
        # shared alphabet = union over queries; sorted at construction, new
        # labels APPEND at live registration (existing adj rows keep their
        # index — the ×4-rounded label slots absorb small growth)
        self.labels: Tuple[str, ...] = tuple(
            sorted(set().union(*[set(q.dfa.labels) for q in queries]))
        )
        self._label_index = {lab: i for i, lab in enumerate(self.labels)}
        self.k = 0           # padded state count; set by _rebuild_tables
        self.max_window = 0.0
        self._rebuild_tables()
        n_label_slots = _round_up(len(self.labels), LABEL_BUCKET)
        self.executor.init_state(self.n_slots, n_label_slots, self.q_cap, self.k)
        # host-side mirror of the device stream clock (decode timestamps
        # without forcing a device sync; identical by construction — both
        # advance by the max event time seen)
        self._host_now = NEG_INF
        # vertex interning (shared across queries: the stream is one graph)
        self.slot_of: Dict[object, int] = {}
        self.vertex_of: List[Optional[object]] = [None] * self.n_slots
        self.free: List[int] = list(range(self.n_slots - 1, -1, -1))
        # slots referenced by the chunk currently being packed: compaction
        # triggered mid-chunk must not recycle them (they may have no
        # adjacency yet and would otherwise look dead)
        self._chunk_pinned: Set[int] = set()
        # deferred-decode FIFO (PendingResults handles not yet resolved);
        # a deque so the drain is O(1) per handle — at async_depth-deep
        # service queues list.pop(0) was O(n) per pop, O(n²) per drain
        self._pending_fifo: Deque[PendingResults] = collections.deque()
        # per-lane results
        self.per_query_results: List[Set[Pair]] = [set() for _ in range(self.q_cap)]
        self.per_query_log: List[List[Tuple[float, Pair]]] = [[] for _ in range(self.q_cap)]
        self.per_query_conflicted: List[bool] = [False] * self.q_cap

    # -- executor-backed accounting (back-compat surface) ---------------------

    @property
    def batched_arrays(self) -> BatchedEngineArrays:
        """The device state (owned by the executor; read-only view)."""
        return self.executor.arrays

    @property
    def host_now(self) -> float:
        """Host mirror of the device stream clock — identical by
        construction (both advance by the max event time seen), so
        maintenance and telemetry paths read this instead of blocking the
        async dispatch chain on ``arrays.now``."""
        return self._host_now

    @property
    def total_rounds(self) -> int:
        """Global closure iterations (max over queries per dispatch)."""
        return self.executor.rounds_total

    @property
    def total_query_rounds(self) -> int:
        """Sum over queries of ACTIVE rounds (convergence-masked)."""
        return self.executor.query_rounds_total

    @property
    def steps(self) -> int:
        """Jitted ingest/delete dispatches (the Q-sharing win)."""
        return self.executor.steps

    # -- lane bookkeeping ----------------------------------------------------

    @property
    def q_cap(self) -> int:
        """Allocated lane capacity (the Q axis of the device arrays)."""
        return len(self.lane_specs)

    @property
    def n_queries(self) -> int:
        """Number of LIVE queries (non-inert lanes)."""
        return sum(1 for s in self.lane_specs if s is not None)

    @property
    def query_specs(self) -> List[RegisteredQuery]:
        """Live query specs in lane order (back-compat view)."""
        return [s for s in self.lane_specs if s is not None]

    def live_items(self) -> List[Tuple[int, RegisteredQuery]]:
        return [(qi, s) for qi, s in enumerate(self.lane_specs) if s is not None]

    def lane_of(self, name: str) -> int:
        for qi, s in enumerate(self.lane_specs):
            if s is not None and s.name == name:
                return qi
        raise KeyError(f"no live query named {name!r}")

    def _rebuild_tables(self) -> None:
        """Recompute the flattened transition table and per-lane metadata
        from the current lane list (inert lanes contribute nothing). K and
        max_window never shrink below live device state / the last retention
        threshold."""
        dfas = [s.dfa if s is not None else _INERT_DFA for s in self.lane_specs]
        self.btt = BatchedTransitionTable.from_dfas(dfas, self.labels, k_min=self.k)
        self.k = self.btt.k
        qc = self.q_cap
        fm = np.zeros((qc, self.k), bool)
        nc = np.zeros((qc, self.k, self.k), bool)
        self._simple = np.zeros((qc,), bool)
        self._check_conflict = np.zeros((qc,), bool)
        windows = np.zeros((qc,), np.float32)
        live = np.zeros((qc,), bool)
        for qi, spec in enumerate(self.lane_specs):
            if spec is None:
                continue
            dfa = spec.dfa
            for f in dfa.finals:
                fm[qi, f] = True
            nc[qi, : dfa.k, : dfa.k] = ~dfa.containment
            windows[qi] = spec.window
            self._simple[qi] = spec.path_semantics == "simple"
            self._check_conflict[qi] = (
                spec.path_semantics == "simple" and not dfa.has_containment_property
            )
            live[qi] = True
        self.finals_mask = jnp.asarray(fm)
        self.not_contained = jnp.asarray(nc)
        self.windows = jnp.asarray(windows)
        self.live_mask = jnp.asarray(live)
        if live.any():
            self.max_window = float(windows[live].max())
        # else: keep the previous retention threshold — with no live queries
        # the shared graph is retained at the last group policy so a future
        # registration still answers over the live window
        self.tables = QueryTables(
            self.btt, self.finals_mask, self.windows, self.live_mask,
            int(live.sum()), float(self.max_window),
        )

    def _repad_arrays(self) -> None:
        """Grow device state in place to the current (q_cap, label-slot, K)
        capacities. Growth only — inert padding is reclaimable, never
        reshaped away — and append-only, so existing lanes/labels/states
        keep their indices and compiled steps are reused within a bucket."""
        self.executor.grow(
            q_cap=self.q_cap,
            k=self.k,
            n_label_slots=_round_up(len(self.labels), LABEL_BUCKET),
        )

    # -- query lifecycle -----------------------------------------------------

    def register_query(self, spec: RegisteredQuery) -> Set[Pair]:
        """Add a persistent query to the LIVE group (works mid-stream).

        Re-pads device state in place (Q bucketed, K to the new
        ``max_q k_q``, label axis on union-alphabet growth), then seeds the
        new lane's closure with one closure pass over the existing shared
        adjacency — only the new lane relaxes; converged lanes stay masked
        (on a mesh executor, whole shards skip). Returns the query's
        INITIAL result pairs (valid over the current window), which are
        recorded as emitted: the subsequent result stream is identical to a
        freshly built group fed the retained graph and then the tail of the
        stream.
        """
        if spec.dfa.containment is None:
            raise ValueError(f"compile query {spec.name!r} with compile_query()")
        if any(s is not None and s.name == spec.name for s in self.lane_specs):
            raise ValueError(f"query {spec.name!r} already registered")
        self._drain_pending()
        # union alphabet growth: append-only
        for lab in sorted(spec.dfa.labels):
            if lab not in self._label_index:
                self._label_index[lab] = len(self.labels)
                self.labels = self.labels + (lab,)
        # lane: reclaim an inert hole, else grow the Q axis to the next
        # bucket (rounded to the executor's lane-shard quantum)
        lane = next((i for i, s in enumerate(self.lane_specs) if s is None), None)
        if lane is None:
            lane = len(self.lane_specs)
            q_quantum = Q_BUCKET * self.executor.q_multiple // math.gcd(
                Q_BUCKET, self.executor.q_multiple)
            new_cap = _round_up(lane + 1, q_quantum)
            grow = new_cap - lane
            self.lane_specs.extend([None] * grow)
            self.per_query_results.extend(set() for _ in range(grow))
            self.per_query_log.extend([] for _ in range(grow))
            self.per_query_conflicted.extend([False] * grow)
        self.lane_specs[lane] = spec
        self._rebuild_tables()
        self._repad_arrays()
        # the lane may be a reclaimed hole: make sure it starts inert
        self.executor.clear_lane(lane)
        self.per_query_results[lane] = set()
        self.per_query_log[lane] = []
        self.per_query_conflicted[lane] = False
        if not self.slot_of:
            return set()  # nothing ingested yet: nothing to seed
        # seed: one closure pass over the EXISTING shared adjacency, only
        # the new lane unmasked (every other lane is already at fixpoint)
        lane_mask = np.zeros((self.q_cap,), bool)
        lane_mask[lane] = True
        self.executor.relax(self.tables, query_mask=lane_mask)
        valid = self.executor.emit(self.tables)
        self.executor.set_lane_emitted(lane, valid[lane])
        if self._check_conflict[lane]:
            a = self.executor.arrays
            low = a.now - self.windows
            flags = np.asarray(_conflict_possible(
                self.executor.dense_dist(), self.not_contained, low))
            if flags[lane]:
                self.per_query_conflicted[lane] = True
        initial = self._decode_pairs(np.asarray(valid[lane]), bool(self._simple[lane]))
        t = self._host_now
        for p in sorted(initial, key=repr):
            self.per_query_results[lane].add(p)
            self.per_query_log[lane].append((t, p))
        return initial

    def deregister_query(self, name: str) -> None:
        """Remove a live query: its lane becomes inert padding (dist/emitted
        cleared, no transitions, window 0) reclaimable by the next
        :meth:`register_query`. Other lanes are untouched — their result
        streams are unaffected by the departure (tested). Capacities (Q, K,
        labels, vertex slots) never shrink; if the departing query held the
        group's largest window, the retention threshold tightens to the
        remaining queries' maximum."""
        lane = self.lane_of(name)
        self._drain_pending()
        self.lane_specs[lane] = None
        self.executor.clear_lane(lane)
        self.per_query_results[lane] = set()
        self.per_query_log[lane] = []
        self.per_query_conflicted[lane] = False
        self._rebuild_tables()

    # -- interning ----------------------------------------------------------

    def _slot(self, vertex: object) -> int:
        s = self.slot_of.get(vertex)
        if s is None:
            if not self.free:
                self.compact()
            if not self.free:
                # grow-on-demand (beyond-paper): double the vertex axis,
                # rounded to the executor's vertex-shard quantum — the
                # engine never raises on capacity mid-stream
                self._grow_slots(
                    _round_up(self.n_slots * 2, self.executor.n_multiple))
            s = self.free.pop()
            self.slot_of[vertex] = s
            self.vertex_of[s] = vertex
        return s

    def _grow_slots(self, new_n: int) -> None:
        """Append-only growth of the vertex axis (adj/dist/emitted re-pad;
        slot indices survive, so the interner and any checkpoint metadata
        remain valid)."""
        if new_n <= self.n_slots:
            return
        self.executor.grow(n_slots=new_n)
        old_n = self.n_slots
        self.n_slots = new_n
        self.vertex_of.extend([None] * (new_n - old_n))
        # existing free slots keep priority (pop from the end)
        self.free = list(range(new_n - 1, old_n - 1, -1)) + self.free

    # -- public API ----------------------------------------------------------

    def insert(self, u: object, v: object, label: str, ts: float) -> List[Set[Pair]]:
        return self.insert_batch([(u, v, label, ts)])

    def insert_batch(
        self, edges: Sequence[Tuple[object, object, str, float]]
    ) -> List[Set[Pair]]:
        """Ingest a micro-batch of append sgts (timestamp-ordered). Returns
        the NEW result pairs per lane (list indexed like lane_specs)."""
        return self.insert_batch_pending(edges).resolve()

    def insert_batch_pending(
        self, edges: Sequence[Tuple[object, object, str, float]]
    ) -> PendingResults:
        """Like :meth:`insert_batch` but returns a :class:`PendingResults`
        handle without forcing the device->host result transfer — the async
        micro-batched decode path (the service overlaps the transfer with
        the next ingest dispatch)."""
        pending = PendingResults(self, self.q_cap)
        self._pending_fifo.append(pending)
        B = self.batch_size
        for i in range(0, len(edges), B):
            self._ingest_chunk(edges[i : i + B], pending)
        return pending

    def _ingest_chunk(self, edges, pending: PendingResults) -> None:
        B = self.batch_size
        src = np.zeros((B,), np.int32)
        dst = np.zeros((B,), np.int32)
        lab = np.zeros((B,), np.int32)
        ts = np.full((B,), NEG_INF, np.float32)
        mask = np.zeros((B,), bool)
        # the stream clock advances from EVERY event in the chunk, packed or
        # not: a mixed chunk whose trailing tuples are out-of-alphabet must
        # not evaluate window validity against a stale `now`
        chunk_now = max(t for (_u, _v, _l, t) in edges)
        j = 0
        self._chunk_pinned.clear()
        try:
            with telemetry.span("engine.intern") as sp:
                for (u, v, label, t) in edges:
                    li = self._label_index.get(label)
                    if li is None:
                        continue  # outside the union Sigma_Q: discarded (paper §5.2)
                    # pin each slot as soon as it is interned: _slot() may
                    # compact mid-chunk, and a chunk-local vertex with no
                    # adjacency yet must not be recycled before its edge lands
                    si = self._slot(u)
                    self._chunk_pinned.add(si)
                    di = self._slot(v)
                    self._chunk_pinned.add(di)
                    src[j] = si
                    dst[j] = di
                    lab[j] = li
                    ts[j] = t
                    mask[j] = True
                    j += 1
                sp.value = j
            self._host_now = max(self._host_now, chunk_now)
            if j == 0:
                self._bypass(len(edges), chunk_now)
                return
            new = self.executor.ingest_batch(
                src, dst, lab, ts, mask, chunk_now, self.tables
            )
        finally:
            self._chunk_pinned.clear()
        if self._check_conflict.any():
            a = self.executor.arrays
            low = a.now - self.windows
            flags = np.asarray(_conflict_possible(
                self.executor.dense_dist(), self.not_contained, low))
            for qi in np.nonzero(flags & self._check_conflict)[0]:
                self.per_query_conflicted[int(qi)] = True
        # decode deferred: snapshot the interner so later slot recycling
        # cannot remap this chunk's pairs
        pending._add(new, list(self.vertex_of), self._host_now)

    def _drain_pending(self, upto: Optional[PendingResults] = None) -> None:
        """Resolve outstanding deferred decodes in dispatch order (through
        ``upto`` when given, else all)."""
        while self._pending_fifo:
            head = self._pending_fifo.popleft()
            head._decode_chunks()
            if head is upto:
                break

    def delete(self, u: object, v: object, label: str, ts: float) -> List[Set[Pair]]:
        """Explicit deletion (negative tuple). Returns invalidated pairs
        per lane."""
        return self.delete_batch([(u, v, label, ts)])

    def delete_batch(
        self, edges: Sequence[Tuple[object, object, str, float]]
    ) -> List[Set[Pair]]:
        """Delete a micro-batch of negative sgts (timestamp-ordered)
        through the same chunked dispatch path as :meth:`insert_batch`: up
        to ``batch_size`` negative tuples share ONE jitted delete dispatch
        (with ``frontier != "off"`` their cones merge into one dirty set).
        Returns the invalidated pairs per lane, unioned over the batch.

        B = 1 matches per-event semantics exactly; B > 1 evaluates each
        chunk's invalidation at the chunk's max event time (the same
        batch-boundary skew contract as :meth:`insert_batch`). Only LIVE
        lanes are decoded — inert padding lanes (deregistered holes, bucket
        growth) return empty sets without an O(N²) scan each, and a stale
        padding lane can never surface pairs."""
        self._drain_pending()
        out: List[Set[Pair]] = [set() for _ in range(self.q_cap)]
        B = self.batch_size
        for i in range(0, len(edges), B):
            self._delete_chunk(edges[i : i + B], out)
        return out

    def _delete_chunk(self, edges, out: List[Set[Pair]]) -> None:
        B = self.batch_size
        src = np.zeros((B,), np.int32)
        dst = np.zeros((B,), np.int32)
        lab = np.zeros((B,), np.int32)
        mask = np.zeros((B,), bool)
        chunk_now = max(t for (_u, _v, _l, t) in edges)
        self._host_now = max(self._host_now, chunk_now)
        j = 0
        with telemetry.span("engine.intern") as sp:
            for (u, v, label, _t) in edges:
                li = self._label_index.get(label)
                if li is None or u not in self.slot_of or v not in self.slot_of:
                    continue  # unknown label/vertex: nothing retained to drop
                src[j] = self.slot_of[u]
                dst[j] = self.slot_of[v]
                lab[j] = li
                mask[j] = True
                j += 1
            sp.value = j
        if j == 0:
            self._bypass(len(edges), chunk_now)
            return
        invalidated = self.executor.delete_batch(
            src, dst, lab, mask, chunk_now, self.tables)
        inv = _fetch_result(invalidated)
        for qi, _spec in self.live_items():
            out[qi] |= self._decode_pairs(inv[qi], bool(self._simple[qi]))

    def _bypass(self, n_events: int, chunk_now: float) -> None:
        """A chunk that packed no tuple still advances the stream clock:
        every event timestamp moves it."""
        with telemetry.span("engine.bypass", n_events):
            self.executor.advance_clock(chunk_now)

    def expire(self, tau: Optional[float] = None) -> None:
        """Slide-boundary maintenance: adjacency masking + slot recycling.
        Safe with deferred decodes outstanding (they snapshot the interner);
        the device dispatch is sequenced after the pending ingests."""
        t = tau if tau is not None else self._host_now
        self._host_now = max(self._host_now, t)
        live = self.executor.expire(t, self.max_window)
        self._recycle(live)

    def compact(self) -> None:
        self.expire()

    def _recycle(self, live: np.ndarray) -> None:
        with telemetry.span("engine.recycle") as sp:
            dead_slots = [
                s for s, vtx in enumerate(self.vertex_of)
                if vtx is not None and not bool(live[s])
                and s not in self._chunk_pinned  # chunk-local: edge not landed yet
            ]
            sp.value = len(dead_slots)
            if not dead_slots:
                return
            self.executor.clear_slots(dead_slots)
            for s in dead_slots:
                vtx = self.vertex_of[s]
                self.vertex_of[s] = None
                del self.slot_of[vtx]
                self.free.append(s)

    # -- result decoding ------------------------------------------------------

    def _decode_pairs(self, mat: np.ndarray, simple: bool) -> Set[Pair]:
        pairs: Set[Pair] = set()
        xs, vs = np.nonzero(mat)
        for x, v in zip(xs.tolist(), vs.tolist()):
            if simple and x == v:
                continue  # a simple path never revisits its source
            xv = self.vertex_of[x]
            vv = self.vertex_of[v]
            if xv is not None and vv is not None:
                pairs.add((xv, vv))
        return pairs

    def _decode_new_into(
        self,
        arr: np.ndarray,                       # (Q, N, N) bool
        vertex_of: List[Optional[object]],     # interner snapshot at dispatch
        t: float,
        fresh: List[Set[Pair]],
    ) -> None:
        """Merge per-lane pairs NEW to the monotone result set into `fresh`:
        after slot recycling the emitted matrices forget old occupants, so
        the device diff may resurface already-reported pairs — the
        python-side sets are the source of truth for implicit-window
        monotonicity.

        Spans: ``engine.decode`` (the whole call, valued in new pairs)
        over ``engine.decode_scan`` (the ``np.nonzero`` scan, valued in
        cells set)."""
        with telemetry.span("engine.decode") as sp:
            with telemetry.span("engine.decode_scan") as scan:
                qs, xs, vs = np.nonzero(arr)
                scan.value = len(qs)
            added = 0
            for q, x, v in zip(qs.tolist(), xs.tolist(), vs.tolist()):
                if self._simple[q] and x == v:
                    continue
                xv = vertex_of[x]
                vv = vertex_of[v]
                if xv is None or vv is None:
                    continue
                p = (xv, vv)
                if p not in self.per_query_results[q]:
                    self.per_query_results[q].add(p)
                    self.per_query_log[q].append((t, p))
                    fresh[q].add(p)
                    added += 1
            sp.value = added

    def current_results(self, qi: int = 0) -> Set[Pair]:
        """Snapshot view (explicit-window semantics) for lane `qi`."""
        valid = self.executor.emit(self.tables)
        return self._decode_pairs(np.asarray(valid[qi]), bool(self._simple[qi]))

    def retained_edges(self) -> List[Tuple[object, object, str, float]]:
        """The shared graph's current content as (u, v, label, ts) tuples in
        timestamp order — everything a newly registered query's seeding
        closure sees. Feeding these into a fresh engine (and syncing its
        clock to this engine's `now`) reproduces this engine's dist for any
        query, because the closure fixpoint depends only on the final
        adjacency: the oracle construction of the churn conformance tests
        and benchmarks/fig13_query_churn.py."""
        adj = np.asarray(jax.device_get(self.executor.dense_adj()))
        out: List[Tuple[object, object, str, float]] = []
        ls, us, vs = np.nonzero(adj > NEG_INF)
        for l, u, v in zip(ls.tolist(), us.tolist(), vs.tolist()):
            if l >= len(self.labels):
                continue
            uu = self.vertex_of[u]
            vv = self.vertex_of[v]
            if uu is None or vv is None:
                continue
            out.append((uu, vv, self.labels[l], float(adj[l, u, v])))
        out.sort(key=lambda e: e[3])
        return out

    def index_size(self, qi: Optional[int] = None) -> Tuple[int, int]:
        """(active roots, populated (x,v,s) entries) — Fig. 5 analogue.
        `qi=None` aggregates over the whole group."""
        a = self.executor.arrays
        # host clock mirror instead of a.now: windows is static (no
        # pending dispatch feeds it), so only the dist read below has to
        # wait on the in-flight closure
        low = self._host_now - np.asarray(self.windows)  # (Q,)
        pop = np.asarray(self.executor.dense_dist()) > low[:, None, None, None]
        if qi is not None:
            pop = pop[qi : qi + 1]
        roots = int(pop.any(axis=(2, 3)).sum())
        return roots, int(pop.sum())

    # -- state persistence (checkpoint/ckpt.py rides this) --------------------

    def state_arrays(self) -> Dict[str, jnp.ndarray]:
        """The device state as one pytree (checkpointable as-is; sharded
        executors hand back globally-addressable arrays that device_get
        gathers)."""
        self._drain_pending()
        a = self.executor.arrays
        return {"adj": self.executor.dense_adj(),
                "dist": self.executor.dense_dist(),
                "emitted": a.emitted, "now": a.now}

    def load_state_arrays(self, state: Dict[str, jnp.ndarray]) -> None:
        """Exact-shape reload (same capacities). For checkpoints written by
        a group with a different churn history (other Q/K/label/slot
        padding), use :meth:`adopt_state`."""
        self._drain_pending()
        self.executor.place({k: np.asarray(jax.device_get(v))
                             for k, v in state.items()})
        self._host_now = float(np.asarray(jax.device_get(state["now"])))

    def adopt_state(
        self,
        state: Dict[str, jnp.ndarray],
        lane_names: Sequence[Optional[str]],
        labels: Sequence[str],
    ) -> None:
        """Load checkpointed device arrays whose Q/K/label/vertex capacities
        may differ from this engine's (bucketed-Q padding, different churn
        history, a vertex axis that grew at runtime, a different executor's
        shard quanta). Lanes are matched by query NAME, adjacency rows by
        label NAME; slot indices are positional (the interner metadata
        refers to them), so the smaller vertex capacity is padded and a
        LARGER checkpoint grows this engine first. The live query sets must
        agree. Labels present only in the checkpoint (e.g. retained from
        queries deregistered pre-snapshot) are appended so the shared graph
        survives intact. Works across executors: a mesh-written checkpoint
        restores onto a local executor and vice versa (arrays are logical;
        placement is the executor's concern)."""
        self._drain_pending()
        adj_ck = np.asarray(jax.device_get(state["adj"]))
        dist_ck = np.asarray(jax.device_get(state["dist"]))
        emitted_ck = np.asarray(jax.device_get(state["emitted"]))
        ck_n = adj_ck.shape[1]
        if ck_n > self.n_slots:
            self._grow_slots(_round_up(ck_n, self.executor.n_multiple))
        ours = {spec.name: qi for qi, spec in self.live_items()}
        theirs = {name: qi for qi, name in enumerate(lane_names) if name is not None}
        if set(ours) != set(theirs):
            raise ValueError(
                f"checkpointed query set {sorted(theirs)} does not match "
                f"registered set {sorted(ours)}"
            )
        for lab in labels:
            if lab not in self._label_index:
                self._label_index[lab] = len(self.labels)
                self.labels = self.labels + (lab,)
        self._rebuild_tables()
        self._repad_arrays()
        a = self.executor.arrays
        adj = np.full(self.executor.adj_shape, NEG_INF, np.float32)
        for li_ck, lab in enumerate(labels):
            adj[self._label_index[lab], :ck_n, :ck_n] = adj_ck[li_ck]
        dist = np.full(self.executor.dist_shape, NEG_INF, np.float32)
        emitted = np.zeros(tuple(a.emitted.shape), bool)
        # states beyond a lane's own dfa.k are provably -inf padding (no
        # transition ever scatters into them), so the K prefix carries
        # everything real in either direction
        kk = min(dist_ck.shape[3], self.k)
        for name, qi in ours.items():
            dist[qi, :ck_n, :ck_n, :kk] = dist_ck[theirs[name], :, :, :kk]
            emitted[qi, :ck_n, :ck_n] = emitted_ck[theirs[name]]
        now = np.float32(np.asarray(jax.device_get(state["now"])))
        self.executor.place(
            {"adj": adj, "dist": dist, "emitted": emitted, "now": now})
        self._host_now = float(now)

    def interner_state(self) -> Dict[str, object]:
        """Vertex interner as JSON-able metadata with TYPE TAGS: string ids
        like "42" and int ids like 42 both survive a snapshot → restore
        round trip (the untyped v1 format guessed int() on load and turned
        numeric-string vertices into ints)."""
        return {
            "format": 2,
            "entries": [
                [_encode_vertex(v), int(slot)]
                for v, slot in sorted(self.slot_of.items(), key=lambda kv: kv[1])
            ],
        }

    def load_interner(self, state: Dict) -> None:
        # v2 detection must not be fooled by a LEGACY checkpoint whose
        # stream contained vertices literally named "format"/"entries"
        # (v1 values are all int slots, never a list)
        if (isinstance(state, dict) and state.get("format") == 2
                and isinstance(state.get("entries"), list)):
            self.slot_of = {
                _decode_vertex(enc): int(slot) for enc, slot in state["entries"]
            }
        else:  # legacy v1 checkpoints: untyped str keys, int guessed on load
            self.slot_of = {_maybe_int(k): v for k, v in state.items()}
        self.vertex_of = [None] * self.n_slots
        for vtx, slot in self.slot_of.items():
            self.vertex_of[slot] = vtx
        used = set(self.slot_of.values())
        self.free = [s for s in range(self.n_slots - 1, -1, -1) if s not in used]

    def results_state(self) -> Dict[str, object]:
        self._drain_pending()
        return {
            "format": 2,
            "results": {
                spec.name: [
                    [_encode_vertex(a), _encode_vertex(b)]
                    for (a, b) in sorted(self.per_query_results[qi], key=repr)
                ]
                for qi, spec in self.live_items()
            },
            "conflicted": {
                spec.name: self.per_query_conflicted[qi]
                for qi, spec in self.live_items()
            },
        }

    def load_results_state(self, state: Dict[str, object]) -> None:
        tagged = state.get("format", 1) >= 2
        for qi, spec in self.live_items():
            pairs = state["results"][spec.name]
            if tagged:
                self.per_query_results[qi] = {
                    (_decode_vertex(a), _decode_vertex(b)) for a, b in pairs
                }
            else:
                self.per_query_results[qi] = {tuple(p) for p in pairs}
            self.per_query_log[qi] = []
            self.per_query_conflicted[qi] = bool(state["conflicted"][spec.name])


def _encode_vertex(v: object) -> List:
    """Type-tagged JSON-able encoding of a vertex id (satellite fix: the
    checkpoint must not guess types on load)."""
    if isinstance(v, bool):  # before int: bool is an int subclass
        return ["b", bool(v)]
    if isinstance(v, int):
        return ["i", int(v)]
    if isinstance(v, float):
        return ["f", float(v)]
    if isinstance(v, str):
        return ["s", v]
    if isinstance(v, tuple):
        return ["t", [_encode_vertex(x) for x in v]]
    import base64
    import pickle

    return ["p", base64.b64encode(pickle.dumps(v)).decode("ascii")]


def _decode_vertex(enc: Sequence) -> object:
    tag, val = enc
    if tag == "b":
        return bool(val)
    if tag == "i":
        return int(val)
    if tag == "f":
        return float(val)
    if tag == "s":
        return str(val)
    if tag == "t":
        return tuple(_decode_vertex(x) for x in val)
    if tag == "p":
        import base64
        import pickle

        return pickle.loads(base64.b64decode(val))
    raise ValueError(f"unknown vertex tag {tag!r}")


def _maybe_int(s: str):
    """Legacy v1 interner decoding (type-guessing; kept for old manifests)."""
    try:
        return int(s)
    except ValueError:
        return s


class DenseRPQEngine(BatchedDenseRPQEngine):
    """Streaming RPQ engine over fixed-capacity dense state — the thin Q=1
    view over the batched core (one registered query).

    path_semantics: "arbitrary" (RAPQ) or "simple" (RSPQ). Simple-path mode
    uses the Mendelzon–Wood tractable class: if the automaton has the suffix
    containment property the dense answer set is provably identical under
    both semantics (DESIGN.md §2); otherwise runtime conflict detection
    flags windows where the dense answer may over-report, and
    ``conflicted`` exposes it (the service layer falls back to the
    reference RSPQ for exactness — the paper's exponential case).
    """

    def __init__(
        self,
        dfa: DFA,
        window: float,
        n_slots: int = 128,
        batch_size: int = 32,
        backend="jnp",
        path_semantics: str = "arbitrary",
        executor: Optional[Executor] = None,
        frontier: str = "off",
        frontier_cap: int = 32,
        adj_layout: str = "dense",
        ell_cap: int = 8,
        dist_layout: str = "dense",
        dist_cap: int = 16,
    ):
        super().__init__(
            [RegisteredQuery("q0", dfa, float(window), path_semantics)],
            n_slots=n_slots, batch_size=batch_size, backend=backend,
            executor=executor, frontier=frontier, frontier_cap=frontier_cap,
            adj_layout=adj_layout, ell_cap=ell_cap,
            dist_layout=dist_layout, dist_cap=dist_cap,
        )
        self.dfa = dfa
        self.window = float(window)
        self.path_semantics = path_semantics
        self.tt = TransitionTable.from_dfa(dfa)  # legacy consumers (dryrun)

    # -- Q=1 adapters --------------------------------------------------------

    @property
    def arrays(self) -> EngineArrays:
        # adj/dist are always presented as canonical dense slabs — legacy
        # consumers (dryrun, examples) are layout-agnostic
        b = self.executor.arrays
        return EngineArrays(self.executor.dense_adj(),
                            self.executor.dense_dist()[0],
                            b.emitted[0], b.now)

    @arrays.setter
    def arrays(self, a: EngineArrays) -> None:
        adj = a.adj
        if self.executor.adj_layout == "ell":
            adj = self.executor.pack_adj(np.asarray(jax.device_get(adj)))
        dist = a.dist[None]
        if self.executor.dist_layout == "row_sparse":
            dist = self.executor.pack_dist(np.asarray(jax.device_get(dist)))
        self.executor.set_arrays(BatchedEngineArrays(
            adj, dist, a.emitted[None], a.now
        ))

    @property
    def results(self) -> Set[Pair]:
        self._drain_pending()
        return self.per_query_results[0]

    @results.setter
    def results(self, value: Set[Pair]) -> None:
        self.per_query_results[0] = set(value)

    @property
    def result_log(self) -> List[Tuple[float, Pair]]:
        return self.per_query_log[0]

    @property
    def conflicted(self) -> bool:
        return self.per_query_conflicted[0]

    @conflicted.setter
    def conflicted(self, value: bool) -> None:
        self.per_query_conflicted[0] = bool(value)

    def insert(self, u: object, v: object, label: str, ts: float) -> Set[Pair]:
        return super().insert_batch([(u, v, label, ts)])[0]

    def insert_batch(self, edges) -> Set[Pair]:
        return super().insert_batch(edges)[0]

    def delete(self, u: object, v: object, label: str, ts: float) -> Set[Pair]:
        return super().delete(u, v, label, ts)[0]

    def current_results(self) -> Set[Pair]:
        return super().current_results(0)

    def index_size(self) -> Tuple[int, int]:
        return super().index_size(0)


def make_churn_oracle(
    dfa: DFA,
    live_group: BatchedDenseRPQEngine,
    window: float,
    n_slots: int,
    path_semantics: str = "arbitrary",
) -> Tuple[DenseRPQEngine, Set[Pair]]:
    """Fresh-engine oracle for a query registered mid-stream — the single
    construction tests/test_query_churn.py and benchmarks/fig13_query_churn
    assert against. Exact by this recipe, in this order:

    1. sync the fresh engine's clock to the live group's `now` BEFORE
       seeding (expire() on the empty engine), so the seed's emitted
       baseline is "valid over the current window" — the same baseline
       :meth:`BatchedDenseRPQEngine.register_query` records;
    2. feed the group's :meth:`~BatchedDenseRPQEngine.retained_edges` as
       ONE batch — exact because the closure fixpoint depends only on the
       final adjacency, and a single evaluation at the synced clock emits
       exactly the live-window-valid pairs (per-tuple replay would also
       emit pairs only valid at interior instants);
    3. replay the tail per-tuple (batch_size=1: no boundary skew).

    Returns (oracle, seed_results); seed_results must equal the live
    registration's initial answer set."""
    retained = live_group.retained_edges()
    oracle = DenseRPQEngine(dfa, window, n_slots=n_slots,
                            batch_size=max(1, len(retained)),
                            path_semantics=path_semantics)
    oracle.expire(live_group.host_now)
    seed = oracle.insert_batch(retained) if retained else set()
    oracle.batch_size = 1
    return oracle, seed
