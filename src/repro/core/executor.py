"""Executor layer: everything device-facing of the batched dense RPQ engine.

The engine (:mod:`repro.core.engine`) is pure orchestration — vertex
interning, query lifecycle, result decoding, checkpoint metadata. The
device state and every jitted dispatch live behind the narrow interface of
:class:`Executor`:

    ingest_batch / delete_batch   one dispatch per micro-batch
    relax                         closure-to-fixpoint in place (lane seeding,
                                  deletion re-derivation)
    emit                          per-query window-valid pairs (device)
    arrays / place / grow         state access, (re)placement, capacity growth
    expire / clear_slots / ...    maintenance ops

Two implementations:

  * :class:`LocalExecutor` — the single-device path, bit-identical to the
    pre-refactor engine (the jitted step functions here ARE the engine's
    old ones, moved verbatim so the jit cache behaves the same).
  * :class:`~repro.distributed.executor.MeshExecutor` — shards the
    ``(Q, N, N, K)`` closure state over a device mesh (Q over ``data``,
    optionally the vertex axis over ``model``) and keeps the per-query
    convergence mask device-resident so converged/inert lanes skip their
    contraction work per shard (convergence-aware dispatch).

Round accounting also lives here (the executor is the only layer that
knows what actually ran): ``rounds_total`` (global closure iterations),
``query_rounds_total`` (sum over queries of ACTIVE rounds under the
convergence mask), and ``unmasked_query_rounds_total`` (what the same
dispatches would have cost with every live lane riding to the global
fixpoint). Benchmarks read these counters instead of re-deriving them —
re-derivation double-counted after lane churn. Counts are accumulated
lazily (device scalars queued, converted on first read) so the streaming
hot path never blocks on a host sync.
"""
from __future__ import annotations

import functools
import time
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import telemetry
from .backend import BackendLike, ContractionBackend, resolve_backend
from .semiring import (
    NEG_INF,
    BatchedTransitionTable,
    batched_closure,
    batched_valid_pairs,
    frontier_closure,
    frontier_delete,
)
from .sparse_adj import (
    EllAdjacency,
    ell_clear_slots,
    ell_delete,
    ell_expire,
    ell_incident,
    ell_insert,
    ell_max_degree,
    ell_to_dense,
    pack_ell,
)
from .sparse_dist import (
    RowSparseDist,
    pack_rows,
    rsd_clear_lane,
    rsd_clear_slots,
    rsd_empty_like,
    rsd_grow_repack,
    rsd_live_entries,
    rsd_row_counts,
    rsd_to_dense,
)

FRONTIER_MODES = ("off", "on", "auto")

#: adjacency representations: "dense" is the canonical (L, N, N) slab,
#: "ell" the blocked-sparse padded-ELL rows + spill ring (sparse_adj.py).
#: The layout is an executor-construction choice, invisible to results —
#: every dispatch is bit-identical across layouts (the conformance suite
#: and docs/invariants.md "bit-identical spill" pin this).
ADJ_LAYOUTS = ("dense", "ell")

#: dist representations: "dense" is the canonical (Q, N, N, K) slab,
#: "row_sparse" the per-(q, x) reachable-set layout (sparse_dist.py) —
#: per-row slot sets plus a bounded overflow table, with the sparse emit
#: that breaks the O(Q·N²) per-event scan. Same contract as ADJ_LAYOUTS:
#: a construction choice, invisible to results (the conformance suite and
#: docs/invariants.md "row-sparse overflow contract" pin this).
DIST_LAYOUTS = ("dense", "row_sparse")


def _next_pow2(n: int) -> int:
    return 1 << max(int(n) - 1, 1).bit_length()


class BatchedEngineArrays(NamedTuple):
    adj: jnp.ndarray      # (L, N, N) f32 shared
    dist: jnp.ndarray     # (Q, N, N, K) f32
    emitted: jnp.ndarray  # (Q, N, N) bool
    now: jnp.ndarray      # () f32


def init_batched_arrays(
    n_slots: int, n_labels: int, n_queries: int, k: int
) -> BatchedEngineArrays:
    return BatchedEngineArrays(
        adj=jnp.full((n_labels, n_slots, n_slots), NEG_INF, jnp.float32),
        dist=jnp.full((n_queries, n_slots, n_slots, k), NEG_INF, jnp.float32),
        emitted=jnp.zeros((n_queries, n_slots, n_slots), bool),
        now=jnp.asarray(NEG_INF, jnp.float32),
    )


class QueryTables(NamedTuple):
    """Per-lane metadata the engine rebuilds at lifecycle events and the
    executor consumes at every dispatch. ``n_live`` is the host-side live
    lane count (for unmasked-regime round accounting); ``max_window`` is
    the group's retention threshold (largest live window, sticky across an
    empty query set) — clock-anchored backends (mxu_bucket) derive their
    level grid from it at every dispatch."""

    btt: BatchedTransitionTable
    finals_mask: jnp.ndarray  # (Q, K) bool
    windows: jnp.ndarray      # (Q,) f32
    live_mask: jnp.ndarray    # (Q,) bool
    n_live: int
    max_window: float = 0.0


# ---------------------------------------------------------------------------
# jitted step functions (pure; shared across LocalExecutor instances so the
# jit cache is process-wide, exactly as when they lived on the engine)
# ---------------------------------------------------------------------------


def apply_batch(arrays: BatchedEngineArrays, src, dst, lab, ts, mask,
                ts_floor):
    """The ingest dispatch prologue, shared by the dense and frontier
    forms on BOTH executors: fold the masked batch into the adjacency
    (newest-timestamp max) and advance the stream clock. Returns
    ``(adj, now)``. The adjacency layout branches at TRACE time (an
    EllAdjacency is a different pytree, so each layout owns its compile
    cache entry): ELL scatters into row slots with in-dispatch spill on
    per-row overflow — same max-fold, same clock."""
    with jax.named_scope("apply_batch"):
        eff_ts = jnp.where(mask, ts, NEG_INF)
        if isinstance(arrays.adj, EllAdjacency):
            adj = ell_insert(arrays.adj, src, dst, lab, eff_ts, mask)
        else:
            adj = arrays.adj.at[lab, src, dst].max(eff_ts, mode="drop")
        now = jnp.maximum(arrays.now, jnp.maximum(jnp.max(eff_ts), ts_floor))
    return adj, now


def drop_batch(arrays: BatchedEngineArrays, src, dst, lab, mask):
    """The delete dispatch prologue on both layouts: clear the masked
    batch's adjacency entries (every stored copy for ELL — row slots AND
    ring). Returns the retained adjacency."""
    with jax.named_scope("drop_batch"):
        if isinstance(arrays.adj, EllAdjacency):
            return ell_delete(arrays.adj, src, dst, lab, mask)
        drop = jnp.where(mask, jnp.asarray(NEG_INF, jnp.float32),
                         arrays.adj[lab, src, dst])
        return arrays.adj.at[lab, src, dst].set(drop, mode="drop")


def emit_new(arrays: BatchedEngineArrays, dist, adj, now, finals_mask,
             windows):
    """The ingest dispatch epilogue, shared likewise: per-query window
    validity at the new clock, diffed against the emitted frontier.
    Returns ``(new_arrays, new)``."""
    with jax.named_scope("emit_new"):
        low = now - windows
        valid = batched_valid_pairs(dist, finals_mask, low)
        new = jnp.logical_and(valid, jnp.logical_not(arrays.emitted))
        emitted = jnp.logical_or(arrays.emitted, valid)
    return BatchedEngineArrays(adj, dist, emitted, now), new


#: rows of a dispatch's ``(Q, N, N)`` result mask that cross to the host
#: compacted, an ``(R, N)`` bool payload (256 KB at N = 1024). A frontier
#: dispatch re-derives a few dozen rows; a result with more set rows than
#: this crosses whole instead (the overflow path of :func:`compact_rows`).
RESULT_ROWS = 256


class CompactRows(NamedTuple):
    """The set rows of a ``(Q, N, N)`` bool mask, gathered on the device
    by :func:`compact_rows`."""

    n_rows: jnp.ndarray   # () int32: rows (q, x) with any bit set
    row_ids: jnp.ndarray  # (R,) int32: the first R of them as q·N + x; -1 pads
    rows: jnp.ndarray     # (R, N) bool: those rows (padding rows all False)


class DispatchResult(NamedTuple):
    """A dispatch's result (new or invalidated pairs) as the engine
    fetches it: the host copies ``compact``; ``mask`` stays on the device
    and crosses only when ``compact.n_rows`` exceeds the compacted rows
    (overflow)."""

    compact: CompactRows
    mask: jnp.ndarray     # (Q, N, N) bool: the whole mask


def compact_rows(mask: jnp.ndarray) -> CompactRows:
    """Row-compact a ``(Q, N, N)`` bool mask: the set rows' ids in
    ascending ``q·N + x`` order and the first ``min(RESULT_ROWS, Q·N)`` of
    those rows gathered, so decoding ``rows`` through ``row_ids`` gives
    the mask's ``np.nonzero`` in its order whenever ``n_rows`` fits. The
    epilogue of every ingest and delete dispatch, on both executors;
    ``RESULT_ROWS`` is read when the dispatch traces."""
    q, n, _ = mask.shape
    with jax.named_scope("compact_rows"):
        flat = mask.reshape(q * n, n)
        row_any = jnp.any(flat, axis=-1)
        n_rows = jnp.sum(row_any, dtype=jnp.int32)
        (row_ids,) = jnp.nonzero(row_any, size=min(RESULT_ROWS, q * n),
                                 fill_value=-1)
        row_ids = row_ids.astype(jnp.int32)
        rows = jnp.where((row_ids >= 0)[:, None],
                         flat[jnp.maximum(row_ids, 0)], False)
    return CompactRows(n_rows, row_ids, rows)


@functools.partial(jax.jit, static_argnames=("backend",), donate_argnums=(0,))
def _ingest(
    arrays: BatchedEngineArrays,
    src: jnp.ndarray,          # (B,) int32 slot ids
    dst: jnp.ndarray,          # (B,) int32
    lab: jnp.ndarray,          # (B,) int32 shared-alphabet label ids
    ts: jnp.ndarray,           # (B,) f32
    mask: jnp.ndarray,         # (B,) bool  (padding)
    ts_floor: jnp.ndarray,     # () f32 max event time of the WHOLE chunk
    btt: BatchedTransitionTable,
    finals_mask: jnp.ndarray,  # (Q, K) bool
    windows: jnp.ndarray,      # (Q,) f32
    live_mask: jnp.ndarray,    # (Q,) bool: False for inert padding lanes
    w_max: jnp.ndarray,        # () f32 group retention threshold
    backend: BackendLike = "jnp",
):
    adj, now = apply_batch(arrays, src, dst, lab, ts, mask, ts_floor)
    dist, rounds, qrounds = batched_closure(
        arrays.dist, adj, btt, backend, query_mask=live_mask,
        now=now, w_max=w_max,
    )
    out, new = emit_new(arrays, dist, adj, now, finals_mask, windows)
    return out, new, rounds, qrounds, compact_rows(new)


@functools.partial(jax.jit, static_argnames=("backend", "f_cap"),
                   donate_argnums=(0,))
def _ingest_frontier(
    arrays: BatchedEngineArrays,
    src: jnp.ndarray,
    dst: jnp.ndarray,
    lab: jnp.ndarray,
    ts: jnp.ndarray,
    mask: jnp.ndarray,
    ts_floor: jnp.ndarray,
    btt: BatchedTransitionTable,
    finals_mask: jnp.ndarray,
    windows: jnp.ndarray,
    live_mask: jnp.ndarray,
    w_max: jnp.ndarray,
    backend: BackendLike = "jnp",
    f_cap: int = 32,
):
    """Frontier-restricted ingest: identical to :func:`_ingest` except the
    closure relaxes only the rows the batch dirtied (seeded in-dispatch
    from the batch itself), falling back to the dense loop when a lane's
    dirty set overflows ``f_cap`` (a runtime bit, not a recompile).
    Results are bit-identical to the dense dispatch by construction."""
    adj, now = apply_batch(arrays, src, dst, lab, ts, mask, ts_floor)
    dist, rounds, qrounds, fstats = frontier_closure(
        arrays.dist, adj, btt, backend, src, mask, f_cap,
        query_mask=live_mask, now=now, w_max=w_max,
    )
    out, new = emit_new(arrays, dist, adj, now, finals_mask, windows)
    return out, new, rounds, qrounds, fstats, compact_rows(new)


@functools.partial(jax.jit, static_argnames=("backend",), donate_argnums=(0,))
def _delete(
    arrays: BatchedEngineArrays,
    src: jnp.ndarray,          # (B,) int32
    dst: jnp.ndarray,
    lab: jnp.ndarray,
    mask: jnp.ndarray,
    ts_now: jnp.ndarray,       # () f32 event time of the negative tuple(s)
    btt: BatchedTransitionTable,
    finals_mask: jnp.ndarray,
    windows: jnp.ndarray,
    live_mask: jnp.ndarray,    # (Q,) bool
    w_max: jnp.ndarray,        # () f32
    backend: BackendLike = "jnp",
):
    """Explicit deletion (negative tuple): clear adjacency entries and
    recompute every query's closure from scratch — the paper's uniform
    machinery (Delete -> ExpiryRAPQ re-derivation) in dense batched form."""
    now = jnp.maximum(arrays.now, ts_now)
    low = now - windows
    valid_before = batched_valid_pairs(arrays.dist, finals_mask, low)
    adj = drop_batch(arrays, src, dst, lab, mask)
    if isinstance(arrays.dist, RowSparseDist):
        dist0 = rsd_empty_like(arrays.dist)
    else:
        dist0 = jnp.full_like(arrays.dist, NEG_INF)
    dist, rounds, qrounds = batched_closure(
        dist0, adj, btt, backend, query_mask=live_mask,
        now=now, w_max=w_max,
    )
    valid_after = batched_valid_pairs(dist, finals_mask, low)
    invalidated = jnp.logical_and(valid_before, jnp.logical_not(valid_after))
    return (BatchedEngineArrays(adj, dist, arrays.emitted, now),
            invalidated, rounds, qrounds, compact_rows(invalidated))


@functools.partial(jax.jit, static_argnames=("backend", "f_cap"),
                   donate_argnums=(0,))
def _delete_frontier(
    arrays: BatchedEngineArrays,
    src: jnp.ndarray,          # (B,) int32
    dst: jnp.ndarray,
    lab: jnp.ndarray,
    mask: jnp.ndarray,
    ts_now: jnp.ndarray,       # () f32 event time of the negative tuple(s)
    btt: BatchedTransitionTable,
    finals_mask: jnp.ndarray,
    windows: jnp.ndarray,
    live_mask: jnp.ndarray,
    w_max: jnp.ndarray,
    backend: BackendLike = "jnp",
    f_cap: int = 32,
):
    """Cone-seeded incremental deletion: identical contract to
    :func:`_delete` except only the rows whose derivations can pass
    through the dropped edges (the cone, computed in-dispatch on the
    pre-delete state) are cleared and re-derived; cone overflow falls back
    to the dense from-scratch loop in-dispatch. Bit-identical to
    :func:`_delete` by the superset argument (semiring.frontier_delete)."""
    now = jnp.maximum(arrays.now, ts_now)
    low = now - windows
    valid_before = batched_valid_pairs(arrays.dist, finals_mask, low)
    adj = drop_batch(arrays, src, dst, lab, mask)
    dist, rounds, qrounds, fstats = frontier_delete(
        arrays.dist, adj, btt, backend, src, mask, f_cap,
        query_mask=live_mask, now=now, w_max=w_max,
    )
    valid_after = batched_valid_pairs(dist, finals_mask, low)
    invalidated = jnp.logical_and(valid_before, jnp.logical_not(valid_after))
    return (BatchedEngineArrays(adj, dist, arrays.emitted, now),
            invalidated, rounds, qrounds, fstats,
            compact_rows(invalidated))


@jax.jit
def _expire(arrays: BatchedEngineArrays, tau: jnp.ndarray, max_window: jnp.ndarray):
    """Lazy expiration at slide boundaries: mask dead adjacency entries and
    report per-slot liveness for python-side slot recycling. Thresholded at
    the group's LARGEST window (an edge live for any query stays); dist
    needs no update (stale entries fall below each query's own read-time
    validity threshold by construction)."""
    now = jnp.maximum(arrays.now, tau)
    low = now - max_window
    if isinstance(arrays.adj, EllAdjacency):
        adj = ell_expire(arrays.adj, low)
        incident = ell_incident(adj)
    else:
        adj = jnp.where(arrays.adj > low, arrays.adj, NEG_INF)
        incident = jnp.maximum(
            jnp.max(adj, axis=(0, 2)),  # outgoing per u
            jnp.max(adj, axis=(0, 1)),  # incoming per v
        )
    live = incident > low
    return BatchedEngineArrays(adj, arrays.dist, arrays.emitted, now), live


@jax.jit
def _clear_slots(arrays: BatchedEngineArrays, slots: jnp.ndarray):
    """Zero out rows/cols of recycled slots (−inf / False) for ALL queries."""
    n = arrays.emitted.shape[1]
    dead = jnp.zeros((n,), bool).at[slots].set(True, mode="drop")
    if isinstance(arrays.adj, EllAdjacency):
        adj = ell_clear_slots(arrays.adj, dead)
    else:
        adj = arrays.adj.at[:, slots, :].set(NEG_INF, mode="drop")
        adj = adj.at[:, :, slots].set(NEG_INF, mode="drop")
    if isinstance(arrays.dist, RowSparseDist):
        dist = rsd_clear_slots(arrays.dist, dead)
    else:
        dist = arrays.dist.at[:, slots, :, :].set(NEG_INF, mode="drop")
        dist = dist.at[:, :, slots, :].set(NEG_INF, mode="drop")
    emitted = arrays.emitted.at[:, slots, :].set(False, mode="drop")
    emitted = emitted.at[:, :, slots].set(False, mode="drop")
    return BatchedEngineArrays(adj, dist, emitted, arrays.now)


# ---------------------------------------------------------------------------
# Executor base = the single-device (local) implementation
# ---------------------------------------------------------------------------


class Executor:
    """Device-facing half of :class:`~repro.core.engine.BatchedDenseRPQEngine`.

    Owns the :class:`BatchedEngineArrays` state, every jitted dispatch over
    it, and the round accounting. Capacity quanta (``q_multiple`` for the
    lane axis, ``n_multiple`` for the vertex axis) tell the engine what
    granularity this executor can shard: the engine rounds its capacities
    up to them (1 for the local path; the data/model mesh extents for
    :class:`~repro.distributed.executor.MeshExecutor`).
    """

    q_multiple: int = 1
    n_multiple: int = 1

    def __init__(self, backend: BackendLike = "jnp",
                 frontier: str = "off", frontier_cap: int = 32,
                 adj_layout: str = "dense", ell_cap: int = 8,
                 spill_cap: int = 256,
                 dist_layout: str = "dense", dist_cap: int = 16,
                 dist_ovf_cap: Optional[int] = None):
        # first-class ContractionBackend; unknown names raise HERE, at
        # construction (they used to fall silently back to the jnp oracle)
        self.backend: ContractionBackend = resolve_backend(backend)
        if frontier not in FRONTIER_MODES:
            raise ValueError(
                f"unknown frontier mode {frontier!r}; known modes: "
                f"{', '.join(FRONTIER_MODES)}")
        if frontier_cap < 1:
            raise ValueError(f"frontier_cap must be >= 1, got {frontier_cap}")
        if adj_layout not in ADJ_LAYOUTS:
            raise ValueError(
                f"unknown adj_layout {adj_layout!r}; known layouts: "
                f"{', '.join(ADJ_LAYOUTS)}")
        if ell_cap < 1:
            raise ValueError(f"ell_cap must be >= 1, got {ell_cap}")
        if spill_cap < 1:
            raise ValueError(f"spill_cap must be >= 1, got {spill_cap}")
        if dist_layout not in DIST_LAYOUTS:
            raise ValueError(
                f"unknown dist_layout {dist_layout!r}; known layouts: "
                f"{', '.join(DIST_LAYOUTS)}")
        if dist_cap < 1:
            raise ValueError(f"dist_cap must be >= 1, got {dist_cap}")
        if dist_ovf_cap is not None and dist_ovf_cap < 1:
            raise ValueError(
                f"dist_ovf_cap must be >= 1, got {dist_ovf_cap}")
        #: adjacency representation ("dense" | "ell"); results are layout-
        #: independent, memory and the seed term are not (sparse_adj.py)
        self.adj_layout = adj_layout
        #: per-(label, u) degree capacity — pow2-bucketed like Q/F so the
        #: jit compile cache is reused; grows ×2 at spill drains
        self.ell_cap = _next_pow2(ell_cap) if ell_cap > 1 else 1
        #: spill-ring capacity — the host budget drains before the ring
        #: can hold this many appends, so no append is ever dropped
        self.spill_cap = _next_pow2(spill_cap)
        self._spill_budget = 0    # inserts dispatched since the last drain
        self._ell_repacks = 0
        self._ell_spill_drains = 0
        self._ell_live_edges: Optional[int] = None  # snapshot at last repack
        #: dist representation ("dense" | "row_sparse"); like adj_layout,
        #: results are layout-independent, memory and the emit scan are not
        #: (sparse_dist.py)
        self.dist_layout = dist_layout
        #: per-(q, x) reachable-set capacity — pow2-bucketed like the other
        #: capacities (rule R2); grows ×2 at overflow drains and whenever a
        #: host pack finds a fuller row
        self.dist_cap = _next_pow2(dist_cap) if dist_cap > 1 else 1
        #: overflow-table row capacity; None = sized at first placement to
        #: cover every row at small scale (the tests' lost == 0 guarantee),
        #: clamped so the table's dense rows stay bounded at large N
        self.dist_ovf_cap = (_next_pow2(dist_ovf_cap)
                             if dist_ovf_cap is not None else None)
        self._dist_budget = 0     # claim bound since the last drain
        self._dist_repacks = 0
        self._dist_drains = 0
        self._dist_lost = 0       # host view; refreshed at drains
        self._dist_live_entries: Optional[int] = None
        #: frontier-restricted ingest: "off" = dense dispatch only (the
        #: pre-PR 5 path, bit-identical), "on" = frontier dispatch at a
        #: FIXED capacity, "auto" = frontier dispatch whose capacity grows
        #: ×2 when overflow fallbacks are observed (compile-cache friendly)
        self.frontier = frontier
        self.frontier_cap = _next_pow2(frontier_cap) if frontier_cap > 1 else 1
        self.steps = 0  # jitted ingest/delete dispatches
        self._arrays: Optional[BatchedEngineArrays] = None
        # (rounds_dev, qrounds_dev, n_live, fstats_dev|None, n_slots,
        # is_delete, (q_cap, f_cap, dispatch perf_counter_ns)) queue:
        # converted lazily so the per-dispatch hot path never blocks on a
        # device->host sync
        self._pending_counts: List[
            Tuple[object, object, int, object, int, bool,
                  Tuple[int, int, int]]] = []
        self._rounds_total = 0
        self._query_rounds_total = 0
        self._unmasked_query_rounds_total = 0
        # frontier telemetry (aggregated from FrontierStats at flush)
        self._frontier_dispatches = 0
        self._frontier_fallbacks = 0
        self._frontier_rows_relaxed = 0
        self._frontier_dense_row_equiv = 0
        self._frontier_seed_rows = 0
        self._frontier_max_lane_rows = 0
        self._frontier_growth_mark = 0
        # deletion-specific split of the same telemetry (deletes also count
        # in the shared aggregates above: one capacity, one growth policy)
        self._frontier_delete_dispatches = 0
        self._frontier_delete_fallbacks = 0

    # -- state ---------------------------------------------------------------

    def init_state(self, n_slots: int, n_label_slots: int, q_cap: int, k: int) -> None:
        # through place() so subclasses apply their sharding from the very
        # first array (a mesh executor must never materialize the full
        # state on one device)
        self.place({
            "adj": np.full((n_label_slots, n_slots, n_slots), NEG_INF, np.float32),
            "dist": np.full((q_cap, n_slots, n_slots, k), NEG_INF, np.float32),
            "emitted": np.zeros((q_cap, n_slots, n_slots), bool),
            "now": np.float32(NEG_INF),
        })

    @property
    def arrays(self) -> BatchedEngineArrays:
        """The device state (global logical view; np.asarray gathers it)."""
        return self._arrays

    def set_arrays(self, arrays: BatchedEngineArrays) -> None:
        self._arrays = arrays

    def place(self, state: Dict[str, object]) -> None:
        """(Re)place host arrays as this executor's device state — the
        checkpoint-restore entry point (engine.adopt_state builds the
        host-side layout, the executor owns placement/sharding). The
        ``adj`` entry is always the canonical DENSE slab — checkpoints are
        layout-agnostic, so a dense save restores into an ELL executor and
        vice versa; an ELL executor packs here (growing ``ell_cap`` ×2
        until the live max degree fits, so a pack never spills)."""
        adj_dev = self.pack_adj(state["adj"])
        self.set_arrays(BatchedEngineArrays(
            adj_dev,
            self.pack_dist(state["dist"]),
            self._put(np.asarray(state["emitted"], bool), "emitted"),
            self._put(np.asarray(state["now"], np.float32), "now"),
        ))

    def pack_adj(self, adj):
        """Host dense slab -> device adjacency in this executor's layout
        (ELL packs after growing ``ell_cap`` ×2 until the live max degree
        fits, so a pack never spills)."""
        adj_np = np.asarray(adj, np.float32)
        if self.adj_layout == "ell":
            need = int((adj_np > NEG_INF).sum(axis=-1).max()) if adj_np.size \
                else 0
            while self.ell_cap < need:
                self.ell_cap *= 2
            out = self._put_adj(pack_ell(adj_np, self.ell_cap, self.spill_cap))
            self._spill_budget = 0
            return out
        return self._put(adj_np, "adj")

    def pack_dist(self, dist):
        """Host dense slab -> device dist in this executor's layout.

        The row-sparse pack grows ``dist_cap`` ×2 until the fullest row
        fits its slots — a host pack never routes a row to the overflow
        table, the same no-spill-at-pack discipline as :meth:`pack_adj`.
        The overflow table is sized once, at first placement: big enough
        that EVERY row can overflow simultaneously at small scale (so
        nothing is ever lost — the conformance tests' invariant), clamped
        at 4096 rows so its dense (R, N·K) payload stays bounded when N
        is large (where the table is pressure relief, not a fallback —
        drains grow ``dist_cap`` before it can fill)."""
        dist_np = np.asarray(dist, np.float32)
        if self.dist_layout == "row_sparse":
            q, n = dist_np.shape[0], dist_np.shape[1]
            need = int((dist_np > NEG_INF).reshape(q, n, -1).sum(-1).max()) \
                if dist_np.size else 0
            while self.dist_cap < need:
                self.dist_cap *= 2
            if self.dist_ovf_cap is None:
                self.dist_ovf_cap = _next_pow2(min(max(q * n, 64), 4096))
            out = self._put_dist(
                pack_rows(dist_np, self.dist_cap, self.dist_ovf_cap))
            self._dist_budget = 0
            return out
        return self._put(dist_np, "dist")

    def _put(self, arr: np.ndarray, name: str):
        return jnp.asarray(arr)

    def _put_adj(self, ell: EllAdjacency) -> EllAdjacency:
        """Device placement for an ELL adjacency pytree (the mesh executor
        overrides to shard the u-row axis over 'model')."""
        return jax.tree_util.tree_map(jnp.asarray, ell)

    def _put_dist(self, sd: RowSparseDist) -> RowSparseDist:
        """Device placement for a row-sparse dist pytree (the mesh executor
        overrides to shard the lane axis over 'data')."""
        return jax.tree_util.tree_map(jnp.asarray, sd)

    def dense_adj(self) -> jnp.ndarray:
        """The adjacency in canonical dense form regardless of layout —
        checkpoints, retained-edge scans and the reference engines read
        this (maintenance paths; the densify is traced jnp, not a sync)."""
        a = self._arrays.adj
        if isinstance(a, EllAdjacency):
            return ell_to_dense(a)
        return a

    @property
    def adj_shape(self) -> Tuple[int, int, int]:
        """Logical dense ``(L, N, N)`` adjacency shape regardless of layout
        (shape metadata only — never densifies or syncs)."""
        a = self._arrays.adj
        if isinstance(a, EllAdjacency):
            return (a.n_labels, a.n_slots, a.n_slots)
        return tuple(a.shape)

    def dense_dist(self) -> jnp.ndarray:
        """The dist in canonical dense ``(Q, N, N, K)`` form regardless of
        layout — checkpoints, conflict probes and the reference engines
        read this (maintenance paths; the densify is traced jnp, not a
        sync)."""
        d = self._arrays.dist
        if isinstance(d, RowSparseDist):
            return rsd_to_dense(d)
        return d

    @property
    def dist_shape(self) -> Tuple[int, int, int, int]:
        """Logical dense ``(Q, N, N, K)`` dist shape regardless of layout
        (shape metadata only — never densifies or syncs)."""
        d = self._arrays.dist
        if isinstance(d, RowSparseDist):
            q, n, _c = d.idx.shape
            return (q, n, n, d.k)
        return tuple(d.shape)

    def grow(self, *, n_slots: Optional[int] = None, q_cap: Optional[int] = None,
             k: Optional[int] = None, n_label_slots: Optional[int] = None) -> None:
        """Grow device state in place (append-only padding: -inf / False).
        Existing lanes/labels/slots/states keep their indices. Shrinking is
        never performed; passing a smaller capacity is a no-op."""
        a = self._arrays
        # no-op check on shape metadata FIRST: the common lifecycle event
        # (reclaiming an inert lane) must not pay a device->host gather
        if isinstance(a.adj, EllAdjacency):
            l_old, n_old = a.adj.n_labels, a.adj.n_slots
        else:
            l_old, n_old = a.adj.shape[0], a.adj.shape[1]
        q_old, _, _, k_old = self.dist_shape
        n_new = max(n_slots or 0, n_old)
        l_new = max(n_label_slots or 0, l_old)
        q_new = max(q_cap or 0, q_old)
        k_new = max(k or 0, k_old)
        if (n_new, l_new, q_new, k_new) == (n_old, l_old, q_old, k_old):
            return
        # densify-before-gather: growth re-places through the canonical
        # dense slab, so an ELL executor re-packs at the new shape (ring
        # drained as a side effect)
        adj = np.asarray(jax.device_get(self.dense_adj()))
        dist = np.asarray(jax.device_get(self.dense_dist()))
        emitted = np.asarray(jax.device_get(a.emitted))
        adj2 = np.full((l_new, n_new, n_new), NEG_INF, np.float32)
        adj2[:l_old, :n_old, :n_old] = adj
        dist2 = np.full((q_new, n_new, n_new, k_new), NEG_INF, np.float32)
        dist2[:q_old, :n_old, :n_old, :k_old] = dist
        emitted2 = np.zeros((q_new, n_new, n_new), bool)
        emitted2[:q_old, :n_old, :n_old] = emitted
        self.place({"adj": adj2, "dist": dist2, "emitted": emitted2,
                    "now": np.asarray(jax.device_get(a.now))})

    # -- dispatches ----------------------------------------------------------

    def ingest_batch(self, src, dst, lab, ts, mask, ts_floor: float,
                     tables: QueryTables):
        """One jitted ingest dispatch for the whole query group. Returns the
        per-query NEW-validity matrix as a :class:`DispatchResult` of
        DEVICE arrays (the engine copies its compacted rows and decodes
        them, possibly deferred so the transfer overlaps the next
        dispatch).

        With ``frontier != "off"`` the dispatch is the frontier-restricted
        one: per-event work scales with the rows the batch dirties, not N
        (overflow falls back to the dense loop in-dispatch; results are
        bit-identical either way)."""
        with telemetry.span("executor.dispatch", len(src)):
            with telemetry.span("executor.reserve"):
                if self.adj_layout == "ell":
                    self._reserve_spill(len(src))
                if self.dist_layout == "row_sparse":
                    self._reserve_dist(self.frontier != "off")
            if self.frontier != "off":
                return self._ingest_frontier_dispatch(
                    src, dst, lab, ts, mask, ts_floor, tables)
            return self._ingest_dispatch(
                src, dst, lab, ts, mask, ts_floor, tables)

    def _ingest_dispatch(self, src, dst, lab, ts, mask, ts_floor: float,
                         tables: QueryTables):
        self._arrays, new, rounds, qrounds, compact = _ingest(
            self._arrays,
            jnp.asarray(src), jnp.asarray(dst), jnp.asarray(lab),
            jnp.asarray(ts), jnp.asarray(mask),
            jnp.asarray(ts_floor, jnp.float32),
            tables.btt, tables.finals_mask, tables.windows, tables.live_mask,
            jnp.asarray(tables.max_window, jnp.float32),
            backend=self.backend,
        )
        self._account(rounds, qrounds, tables.n_live)
        self.steps += 1
        return DispatchResult(compact, new)

    def _ingest_frontier_dispatch(self, src, dst, lab, ts, mask,
                                  ts_floor: float, tables: QueryTables):
        (self._arrays, new, rounds, qrounds, fstats,
         compact) = _ingest_frontier(
            self._arrays,
            jnp.asarray(src), jnp.asarray(dst), jnp.asarray(lab),
            jnp.asarray(ts), jnp.asarray(mask),
            jnp.asarray(ts_floor, jnp.float32),
            tables.btt, tables.finals_mask, tables.windows, tables.live_mask,
            jnp.asarray(tables.max_window, jnp.float32),
            backend=self.backend, f_cap=self.frontier_cap,
        )
        self._account(rounds, qrounds, tables.n_live, fstats)
        self.steps += 1
        return DispatchResult(compact, new)

    def delete_batch(self, src, dst, lab, mask, ts_now: float,
                     tables: QueryTables):
        """Explicit deletion dispatch; returns the invalidated-pairs matrix
        as a :class:`DispatchResult` (device).

        With ``frontier != "off"`` the dispatch is the cone-seeded
        incremental one: only rows whose derivations can pass through the
        dropped edges are cleared and re-derived (overflow falls back to
        the dense from-scratch loop in-dispatch; results are bit-identical
        either way)."""
        with telemetry.span("executor.dispatch", len(src)):
            with telemetry.span("executor.reserve"):
                if self.dist_layout == "row_sparse":
                    self._reserve_dist(self.frontier != "off")
            if self.frontier != "off":
                return self._delete_frontier_dispatch(
                    src, dst, lab, mask, ts_now, tables)
            return self._delete_dispatch(src, dst, lab, mask, ts_now, tables)

    def _delete_dispatch(self, src, dst, lab, mask, ts_now: float,
                         tables: QueryTables):
        self._arrays, invalidated, rounds, qrounds, compact = _delete(
            self._arrays,
            jnp.asarray(src), jnp.asarray(dst), jnp.asarray(lab),
            jnp.asarray(mask), jnp.asarray(ts_now, jnp.float32),
            tables.btt, tables.finals_mask, tables.windows, tables.live_mask,
            jnp.asarray(tables.max_window, jnp.float32),
            backend=self.backend,
        )
        self._account(rounds, qrounds, tables.n_live)
        self.steps += 1
        return DispatchResult(compact, invalidated)

    def _delete_frontier_dispatch(self, src, dst, lab, mask, ts_now: float,
                                  tables: QueryTables):
        (self._arrays, invalidated, rounds, qrounds, fstats,
         compact) = _delete_frontier(
            self._arrays,
            jnp.asarray(src), jnp.asarray(dst), jnp.asarray(lab),
            jnp.asarray(mask), jnp.asarray(ts_now, jnp.float32),
            tables.btt, tables.finals_mask, tables.windows, tables.live_mask,
            jnp.asarray(tables.max_window, jnp.float32),
            backend=self.backend, f_cap=self.frontier_cap,
        )
        self._account(rounds, qrounds, tables.n_live, fstats, is_delete=True)
        self.steps += 1
        return DispatchResult(compact, invalidated)

    def relax(self, tables: QueryTables,
              query_mask: Optional[np.ndarray] = None) -> None:
        """Run the batched closure to fixpoint in place (no adjacency
        change): lane seeding at registration (``query_mask`` = just the new
        lane) or any state re-derivation."""
        if self.dist_layout == "row_sparse":
            self._reserve_dist(False)
        a = self._arrays
        mask = tables.live_mask if query_mask is None else jnp.asarray(
            np.asarray(query_mask, bool))
        dist, rounds, qrounds = batched_closure(
            a.dist, a.adj, tables.btt, self.backend, query_mask=mask,
            now=a.now, w_max=jnp.asarray(tables.max_window, jnp.float32),
        )
        self._arrays = a._replace(dist=dist)
        self._account(rounds, qrounds, tables.n_live)

    def emit(self, tables: QueryTables) -> jnp.ndarray:
        """(Q, N, N) bool device matrix of pairs valid over each query's
        window at the current stream clock."""
        a = self._arrays
        low = a.now - tables.windows
        return batched_valid_pairs(a.dist, tables.finals_mask, low)

    def expire(self, tau: float, max_window: float) -> np.ndarray:
        self._arrays, live = _expire(
            self._arrays, jnp.asarray(tau, jnp.float32),
            jnp.asarray(max_window, jnp.float32),
        )
        with telemetry.span("executor.sync.expire_live"):
            return np.asarray(live)

    def clear_slots(self, slots: Sequence[int]) -> None:
        self._arrays = _clear_slots(
            self._arrays, jnp.asarray(list(slots), jnp.int32)
        )

    def clear_lane(self, lane: int) -> None:
        a = self._arrays
        if isinstance(a.dist, RowSparseDist):
            dist = rsd_clear_lane(a.dist, jnp.asarray(lane, jnp.int32))
        else:
            dist = a.dist.at[lane].set(NEG_INF)
        self._arrays = a._replace(
            dist=dist,
            emitted=a.emitted.at[lane].set(False),
        )

    def set_lane_emitted(self, lane: int, valid_lane: jnp.ndarray) -> None:
        a = self._arrays
        self._arrays = a._replace(emitted=a.emitted.at[lane].set(valid_lane))

    def advance_clock(self, ts: float) -> None:
        a = self._arrays
        self._arrays = a._replace(
            now=jnp.maximum(a.now, jnp.asarray(ts, jnp.float32))
        )

    # -- ELL spill budget ----------------------------------------------------
    #
    # The ring never drops an append: each ingest dispatch of width B can
    # append at most B ring entries, so the host tracks a conservative
    # budget of appends since the last drain and syncs the ring cursor
    # BEFORE a dispatch could overflow it. A drain that finds the ring
    # occupied means some row overflowed its degree capacity — grow
    # ``ell_cap`` ×2 toward the true max degree and re-pack (which empties
    # the ring). A drain that finds it empty just resets the budget. The
    # sync is explicit (jax.device_get — rule R5's sanctioned form) and
    # amortized: steady-state streams without degree growth never sync.

    def _reserve_spill(self, b: int) -> None:
        bneed = _next_pow2(2 * max(b, 1))
        grew = False
        while self.spill_cap < bneed:
            self.spill_cap *= 2
            grew = True
        if grew:
            self._repack_ell()
        elif self._spill_budget + b > self.spill_cap:
            self._drain_spill()
        self._spill_budget += b

    def _drain_spill(self) -> None:
        self._ell_spill_drains += 1
        with telemetry.span("executor.sync.drain_spill"):
            ptr = int(jax.device_get(self._arrays.adj.spill_ptr))
            need = (int(jax.device_get(ell_max_degree(self._arrays.adj)))
                    if ptr > 0 else 0)
        if ptr > 0:
            while self.ell_cap < need:
                self.ell_cap *= 2
            self._repack_ell()
        else:
            self._spill_budget = 0

    def _repack_ell(self) -> None:
        """Host round-trip re-pack at the current capacities: densify on
        device, re-pack rows (ring folded in, then emptied). Growth and
        compaction reuse this; dist/emitted stay resident."""
        dense = np.asarray(jax.device_get(ell_to_dense(self._arrays.adj)))
        need = int((dense > NEG_INF).sum(axis=-1).max()) if dense.size else 0
        while self.ell_cap < need:
            self.ell_cap *= 2
        self._arrays = self._arrays._replace(
            adj=self._put_adj(pack_ell(dense, self.ell_cap, self.spill_cap)))
        self._ell_repacks += 1
        self._ell_live_edges = int((dense > NEG_INF).sum())
        self._spill_budget = 0

    @property
    def adjacency_stats(self) -> Dict[str, object]:
        """Adjacency-representation telemetry (host-known values only —
        reading this never syncs the device stream). ``live_edges`` and
        ``occupancy`` are snapshots from the last re-pack (None before
        one); ``adj_bytes`` is the exact device footprint of the current
        representation."""
        a = self._arrays.adj if self._arrays is not None else None
        if isinstance(a, EllAdjacency):
            slot_cells = a.n_labels * a.n_slots * a.ell_cap
            adj_bytes = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                            for x in a)
        else:
            slot_cells = int(np.prod(a.shape)) if a is not None else 0
            adj_bytes = slot_cells * 4
        return {
            "layout": self.adj_layout,
            "ell_cap": self.ell_cap,
            "spill_cap": self.spill_cap,
            "repacks": self._ell_repacks,
            "spill_drains": self._ell_spill_drains,
            "live_edges": self._ell_live_edges,
            "slot_cells": slot_cells,
            "adj_bytes": adj_bytes,
            "occupancy": (self._ell_live_edges / slot_cells
                          if self._ell_live_edges is not None and slot_cells
                          else None),
        }

    # -- row-sparse dist overflow budget -------------------------------------
    #
    # Same shape as the ELL spill budget above, at row granularity: the
    # overflow table never silently grows stale — the host tracks a
    # conservative bound on table claims since the last drain (a frontier
    # dispatch can claim at most its frontier rows; a dense round trip can
    # re-pack up to every row) and syncs the claim cursor BEFORE the bound
    # crosses the table capacity. A drain that finds claims means rows
    # outgrew ``dist_cap`` — grow it ×2 toward the observed max row
    # occupancy and re-pack in place (rsd_grow_repack: no densify), which
    # empties the table. A drain that finds the table empty just resets
    # the budget. While the table can hold every row at once (the default
    # sizing at small scale), nothing can EVER be lost; at large N the
    # clamped table plus these drains keep pressure near zero, and any
    # loss is counted (``dist_stats["lost"]``), never silent.

    def _reserve_dist(self, frontier: bool) -> None:
        q, n = self.dist_shape[0], self.dist_shape[1]
        w = q * min(self.frontier_cap, n) if frontier else q * n
        w = min(w, self.dist_ovf_cap)
        if self._dist_budget + w > self.dist_ovf_cap:
            self._drain_dist()
        self._dist_budget += w

    def _drain_dist(self) -> None:
        self._dist_drains += 1
        d = self._arrays.dist
        with telemetry.span("executor.sync.drain_dist"):
            ptr, lost = (int(x) for x in jax.device_get((d.ovf_ptr, d.lost)))
            need = (int(jax.device_get(jnp.max(rsd_row_counts(d))))
                    if ptr > 0 else 0)
        self._dist_lost = lost
        if ptr > 0:
            while self.dist_cap < need:
                self.dist_cap *= 2
            self._repack_dist()
        else:
            self._dist_budget = 0

    def _repack_dist(self) -> None:
        """In-place re-pack at the current capacities (rsd_grow_repack —
        no densify round trip): overflow rows that now fit move into their
        slots, the table empties. Growth and drains reuse this; adj and
        emitted stay resident."""
        d = self._arrays.dist
        sd = rsd_grow_repack(d, self.dist_cap, self.dist_ovf_cap)
        self._arrays = self._arrays._replace(dist=self._put_dist(sd))
        self._dist_repacks += 1
        self._dist_live_entries = int(jax.device_get(rsd_live_entries(sd)))
        self._dist_budget = 0

    @property
    def dist_stats(self) -> Dict[str, object]:
        """Dist-representation telemetry (host-known values only — reading
        this never syncs the device stream). ``live_entries`` and
        ``occupancy`` are snapshots from the last re-pack (None before
        one); ``lost`` is the host's view from the last drain (rows
        dropped with the overflow table full — 0 whenever the table
        covers every row); ``dist_bytes`` is the exact device footprint
        of the current representation."""
        d = self._arrays.dist if self._arrays is not None else None
        if isinstance(d, RowSparseDist):
            slot_cells = d.n_lanes * d.n_slots * d.dist_cap
            dist_bytes = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                             for x in d)
        else:
            slot_cells = int(np.prod(d.shape)) if d is not None else 0
            dist_bytes = slot_cells * 4
        return {
            "layout": self.dist_layout,
            "dist_cap": self.dist_cap,
            "ovf_cap": self.dist_ovf_cap,
            "repacks": self._dist_repacks,
            "drains": self._dist_drains,
            "lost": self._dist_lost,
            "live_entries": self._dist_live_entries,
            "slot_cells": slot_cells,
            "dist_bytes": dist_bytes,
            "occupancy": (self._dist_live_entries / slot_cells
                          if self._dist_live_entries is not None and slot_cells
                          else None),
        }

    # -- round accounting ----------------------------------------------------

    def _account(self, rounds, qrounds, n_live: int, fstats=None,
                 is_delete: bool = False) -> None:
        q, n = self.dist_shape[:2] if self._arrays is not None else (0, 0)
        self._pending_counts.append(
            (rounds, qrounds, n_live, fstats, n, is_delete,
             (q, self.frontier_cap, time.perf_counter_ns())))
        # auto-frontier flushes more eagerly: the ×2 capacity growth reads
        # the flushed overflow telemetry, and reacting a couple hundred
        # dispatches late would strand the stream on the dense fallback
        limit = 64 if self.frontier == "auto" else 256
        if len(self._pending_counts) >= limit:
            self._flush_counts()

    def _flush_counts(self) -> None:
        if self._pending_counts:
            with telemetry.span("executor.sync.flush_counts",
                                len(self._pending_counts)):
                for (rounds, qrounds, n_live, fstats, n, is_delete,
                     slab) in self._pending_counts:
                    self._consume_count(rounds, qrounds, n_live)
                    self._consume_frontier(fstats, rounds, n_live, n,
                                           is_delete, slab)
            self._pending_counts.clear()
        self._maybe_grow_frontier()

    def _consume_count(self, rounds, qrounds, n_live: int) -> None:
        r = int(np.asarray(rounds))
        self._rounds_total += r
        self._query_rounds_total += int(np.asarray(qrounds).sum())
        self._unmasked_query_rounds_total += n_live * r

    def _consume_frontier(self, fstats, rounds, n_live: int, n: int,
                          is_delete: bool = False,
                          slab: Tuple[int, int, int] = (0, 0, 0)) -> None:
        """Aggregate one dispatch's FrontierStats. Works on scalar stats
        (local) and per-shard arrays (mesh) alike: sums/maxes reduce both.

        Also counts, stamped with the dispatch's time, the rows of the
        ``(q_cap, f_cap)`` slab its rounds carried
        (``frontier.slab_rows``: q_cap · f_cap · rounds) and the rows
        they relaxed (``frontier.rows_relaxed``), over the shards that
        took the frontier branch (a dense fallback carries no slab)."""
        if fstats is None:
            return
        q_cap, f_cap, t_ns = slab
        r_sh, fb_sh, rr_sh = np.broadcast_arrays(
            np.asarray(rounds).astype(np.int64).reshape(-1),
            np.asarray(fstats.fell_back).reshape(-1),
            np.asarray(fstats.rows_relaxed).astype(np.int64).reshape(-1))
        kept = ~fb_sh
        if kept.any():
            telemetry.count("frontier.slab_rows",
                            q_cap // r_sh.size * f_cap
                            * int(r_sh[kept].sum()), t_ns)
            telemetry.count("frontier.rows_relaxed",
                            int(rr_sh[kept].sum()), t_ns)
        self._frontier_dispatches += 1
        fell = int(np.asarray(fstats.fell_back).astype(np.int64).sum())
        self._frontier_fallbacks += fell
        if is_delete:
            self._frontier_delete_dispatches += 1
            self._frontier_delete_fallbacks += fell
        self._frontier_rows_relaxed += int(
            np.asarray(fstats.rows_relaxed).astype(np.int64).sum())
        self._frontier_seed_rows += int(
            np.asarray(fstats.seed_rows).astype(np.int64).sum())
        self._frontier_max_lane_rows = max(
            self._frontier_max_lane_rows,
            int(np.asarray(fstats.max_lane_rows).max()))
        # what a dense loop of the same dispatch relaxes: every live lane
        # rides every round over all N rows (occupancy denominator; for a
        # mesh dispatch `rounds` is per-shard — the max is the sync count)
        r = int(np.asarray(rounds).max())
        self._frontier_dense_row_equiv += n_live * n * r

    def _maybe_grow_frontier(self) -> None:
        """``frontier="auto"``: grow the frontier capacity ×2 toward the
        largest observed lane frontier whenever new overflow fallbacks were
        flushed. Capacity is a trace-time shape, so growth means one new
        compile per ×2 step — the same bucketing discipline as Q/K."""
        if self.frontier != "auto":
            return
        if self._frontier_fallbacks <= self._frontier_growth_mark:
            return
        self._frontier_growth_mark = self._frontier_fallbacks
        n = (self.dist_shape[1]
             if self._arrays is not None else self._frontier_max_lane_rows)
        limit = _next_pow2(n)
        target = min(_next_pow2(max(self._frontier_max_lane_rows,
                                    self.frontier_cap * 2)), limit)
        while self.frontier_cap < target:
            self.frontier_cap *= 2

    @property
    def frontier_stats(self) -> Dict[str, object]:
        """Aggregate frontier telemetry: dispatches taken (ingest and
        delete; the delete split is also reported on its own), overflow
        fallbacks, rows relaxed (summed over rounds) vs the dense-loop row
        equivalent, seed occupancy, and the current capacity.

        ``occupancy`` is ``None`` — NOT 0.0 — when no dense-row-equivalent
        work was observed: an all-idle dispatch window carries no signal
        about how full frontiers run, and downstream health checks
        (service.adapt_batch) must not read it as "frontier doing great"."""
        self._flush_counts()
        dense_rows = self._frontier_dense_row_equiv
        return {
            "mode": self.frontier,
            "cap": self.frontier_cap,
            "dispatches": self._frontier_dispatches,
            "fallbacks": self._frontier_fallbacks,
            "delete_dispatches": self._frontier_delete_dispatches,
            "delete_fallbacks": self._frontier_delete_fallbacks,
            "rows_relaxed": self._frontier_rows_relaxed,
            "dense_row_equiv": dense_rows,
            "seed_rows": self._frontier_seed_rows,
            "max_lane_rows": self._frontier_max_lane_rows,
            "occupancy": (self._frontier_rows_relaxed / dense_rows
                          if dense_rows else None),
        }

    @property
    def rounds_total(self) -> int:
        """Global closure iterations (each dispatch's loop runs until its
        slowest participating query converges)."""
        self._flush_counts()
        return self._rounds_total

    @property
    def query_rounds_total(self) -> int:
        """Sum over queries of ACTIVE rounds (per-query convergence mask)."""
        self._flush_counts()
        return self._query_rounds_total

    @property
    def unmasked_query_rounds_total(self) -> int:
        """What the same dispatches would cost with every live lane riding
        to the global fixpoint — accumulated with the live count at each
        dispatch, so mid-stream lane churn cannot skew the comparison."""
        self._flush_counts()
        return self._unmasked_query_rounds_total


class LocalExecutor(Executor):
    """Single-device executor: the pre-refactor engine behavior, verbatim."""
