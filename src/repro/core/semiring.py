"""(max, min) bottleneck-semiring relaxation over the product graph.

The dense Δ index is ``dist[x, v, s]`` = best (max over paths) bottleneck
(min over edges) timestamp of any path x→v whose label drives the DFA from
s0 to s (DESIGN.md §2). One *relaxation round* applies every DFA transition
(s, l, t):

    out[x, v, t] ∨= max_u min(dist[x, u, s], adj[l, u, v])     (∨ = max)

plus the *base* term for transitions out of s0 (seed paths of length 1):

    out[x, v, t] ∨= adj[l, x, v]          for (s0, l, t)

The closure iterates rounds to a fixpoint (monotone, so `lax.while_loop`
on a changed-flag terminates in at most product-graph-diameter rounds).

Every round is parameterized by a :class:`~repro.core.backend.ContractionBackend`
object (PR 4) — ``jnp`` oracle, fused-batched ``pallas`` VPU kernel, or the
level-quantized ``mxu_bucket`` MXU mode. Plain strings are accepted and
VALIDATED (unknown names raise; they used to fall back to jnp silently).
The closure entry points additionally thread ``now``/``w_max`` so a backend
whose operand representation is anchored to the stream clock (the bucket
level grid) can ``prepare_state``/``decode_state`` at the dispatch
boundary; the round loop itself never leaves the backend's representation.

Since PR 5 the ingest closure also comes in a FRONTIER-RESTRICTED form
(:func:`frontier_closure` / :func:`shard_frontier_closure`): only the
source rows a micro-batch dirties are gathered and relaxed, making
per-event work O(J·F·N²) instead of O(J·N³) on low-degree windows, with an
in-dispatch dense fallback on frontier overflow (bit-identical results
always — see the frontier section below). PR 6 extends the same machinery
to explicit DELETIONS (:func:`frontier_delete` /
:func:`shard_frontier_delete`): the deleted edge's cone — the rows whose
derivations can pass through it — is the same reachability reduction run
against the pre-delete state, so deletes are cone-cleared and re-derived
at frontier prices instead of resetting every row.
"""
from __future__ import annotations

from typing import List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .backend import BackendLike, ContractionBackend, resolve_backend
from .sparse_adj import EllAdjacency, ell_label_rows, ell_rows_dense
from .sparse_dist import (RowSparseDist, rsd_from_dense, rsd_gather_rows,
                          rsd_scatter_rows, rsd_seed_gathered, rsd_to_dense,
                          rsd_valid_pairs)

NEG_INF = float("-inf")


class TransitionTable(NamedTuple):
    """Static DFA transition arrays (built once at query registration)."""

    src: jnp.ndarray      # (J,) int32 source state of each transition
    lab: jnp.ndarray      # (J,) int32 label index
    dst: jnp.ndarray      # (J,) int32 destination state
    dst_onehot: jnp.ndarray  # (J, K) f32 one-hot of dst (for scatter-max)
    start_mask: jnp.ndarray  # (J,) bool: src == s0
    k: int
    n_labels: int

    @staticmethod
    def from_dfa(dfa) -> "TransitionTable":
        trans = dfa.transitions()
        if not trans:
            trans = [(0, 0, 0)]  # degenerate: empty language; never fires
            src = np.array([0], np.int32)
            lab = np.array([0], np.int32)
            dst = np.array([0], np.int32)
            oh = np.zeros((1, max(dfa.k, 1)), np.float32)
            return TransitionTable(
                jnp.asarray(src), jnp.asarray(lab), jnp.asarray(dst),
                jnp.asarray(oh), jnp.asarray(np.array([False])),
                max(dfa.k, 1), max(dfa.n_labels, 1),
            )
        src = np.array([s for (s, _l, _t) in trans], np.int32)
        lab = np.array([l for (_s, l, _t) in trans], np.int32)
        dst = np.array([t for (_s, _l, t) in trans], np.int32)
        oh = np.zeros((len(trans), dfa.k), np.float32)
        oh[np.arange(len(trans)), dst] = 1.0
        return TransitionTable(
            src=jnp.asarray(src),
            lab=jnp.asarray(lab),
            dst=jnp.asarray(dst),
            dst_onehot=jnp.asarray(oh),
            start_mask=jnp.asarray(src == dfa.start),
            k=dfa.k,
            n_labels=dfa.n_labels,
        )


def relax_round(
    dist: jnp.ndarray,          # (N, N, K) in the backend's representation
    adj: jnp.ndarray,           # (L, N, N)
    tt: TransitionTable,
    backend: BackendLike = "jnp",
) -> jnp.ndarray:
    """One relaxation round; returns the pointwise max of dist and all
    transition contributions (monotone). Operands are in the backend's
    representation (f32 timestamps for jnp/pallas, int32 levels for
    mxu_bucket — callers of the raw round encode themselves; the closure
    entry points do it via ``prepare_state``)."""
    backend = resolve_backend(backend)
    zero = jnp.asarray(backend.zero, dist.dtype)

    def per_transition(j, acc):
        s = tt.src[j]
        l = tt.lab[j]
        dist_s = jax.lax.dynamic_index_in_dim(
            jnp.moveaxis(dist, 2, 0), s, axis=0, keepdims=False
        )  # (N, N) [x, u]
        adj_l = jax.lax.dynamic_index_in_dim(adj, l, axis=0, keepdims=False)
        contrib = backend.contract(dist_s, adj_l)             # (N, N) [x, v]
        # base term: seed (x, x, s0) = +inf => min(+inf, adj[l, x, v]) = adj
        contrib = jnp.where(tt.start_mask[j], jnp.maximum(contrib, adj_l), contrib)
        # scatter-max into destination state slice
        oh = tt.dst_onehot[j]                                  # (K,)
        upd = jnp.where(oh[None, None, :] > 0, contrib[:, :, None], zero)
        return jnp.maximum(acc, upd)

    out = jax.lax.fori_loop(0, tt.src.shape[0], per_transition, dist)
    return out


def closure(
    dist: jnp.ndarray,
    adj: jnp.ndarray,
    tt: TransitionTable,
    backend: BackendLike = "jnp",
    max_rounds: int = 0,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Iterate relaxation to fixpoint. Returns (dist, rounds_used).

    max_rounds=0 -> bound by N*K (longest simple product path)."""
    backend = resolve_backend(backend)
    n, _, k = dist.shape
    bound = max_rounds if max_rounds > 0 else n * k + 1

    def cond(carry):
        _d, changed, it = carry
        return jnp.logical_and(changed, it < bound)

    def body(carry):
        d, _changed, it = carry
        nd = relax_round(d, adj, tt, backend)
        return nd, jnp.any(nd > d), it + 1

    dist0 = relax_round(dist, adj, tt, backend)
    dist_f, _, rounds = jax.lax.while_loop(
        cond, body, (dist0, jnp.asarray(True), jnp.asarray(1, jnp.int32))
    )
    return dist_f, rounds


def valid_pairs(
    dist: jnp.ndarray, finals: jnp.ndarray, low: jnp.ndarray
) -> jnp.ndarray:
    """(N, N) bool: pair (x, v) has an accepting path fully inside the
    window, i.e. max over final states of dist > low. `finals` is a (K,)
    bool mask."""
    acc = jnp.where(finals[None, None, :], dist, NEG_INF)
    best = jnp.max(acc, axis=2)
    return best > low


# ---------------------------------------------------------------------------
# Multi-query batched formulation
#
# All registered queries share one (L, N, N) adjacency over the UNION label
# alphabet; per-query closure state is stacked into dist (Q, N, N, K) with K
# padded to max_q k_q (padding states are inert: no transition ever scatters
# into them and finals masks are padded False). The per-query DFA transition
# tables are flattened into ONE global transition list — `qidx` names the
# owning query, `lab` indexes the shared alphabet — so a relaxation round is
# a single gather -> batched max-min contraction -> segment-max scatter, and
# one jitted step evaluates every query.
# ---------------------------------------------------------------------------


class BatchedTransitionTable(NamedTuple):
    """Flattened transition arrays of Q stacked DFAs (built at registration).

    J = total transitions across all queries, rounded UP to a bucket
    multiple so different query mixes reuse the same compiled step (J and K
    are trace-time shapes; without bucketing every registration set would
    recompile the closure). Padding rows are inert (`active` False -> their
    contribution is -inf, the semiring zero); padded K states are inert
    because no transition scatters into them and finals masks pad False.
    Queries with an empty language contribute no rows.
    """

    qidx: jnp.ndarray        # (J,) int32 owning query
    src: jnp.ndarray         # (J,) int32 source DFA state (< k_q)
    lab: jnp.ndarray         # (J,) int32 label index in the SHARED alphabet
    dst: jnp.ndarray         # (J,) int32 destination DFA state
    start_mask: jnp.ndarray  # (J,) bool: src == s0 of the owning query
    active: jnp.ndarray      # (J,) bool: False for shape-padding rows
    n_queries: int
    k: int                   # K_max (padded per-query state count)
    n_labels: int            # |union alphabet|

    @staticmethod
    def from_dfas(
        dfas: Sequence, labels: Sequence[str],
        j_bucket: int = 8, k_bucket: int = 2, k_min: int = 1,
    ) -> "BatchedTransitionTable":
        """Stack per-query DFAs over a shared label alphabet.

        ``k_min`` floors the padded state count: a live engine whose device
        state already has K state slots passes ``k_min=K`` so deregistering
        its deepest query never *shrinks* the table below the allocated dist
        axis (the extra states are inert padding either way).
        """
        labels = tuple(labels)
        lab_index = {lab: i for i, lab in enumerate(labels)}
        k_max = max([d.k for d in dfas] + [1, k_min])
        k_max += (-k_max) % k_bucket
        qidx, src, lab, dst, start = [], [], [], [], []
        for q, dfa in enumerate(dfas):
            for (s, li, t) in dfa.transitions():
                qidx.append(q)
                src.append(s)
                lab.append(lab_index[dfa.labels[li]])
                dst.append(t)
                start.append(s == dfa.start)
        n_active = len(qidx)
        n_rows = max(n_active + (-n_active) % j_bucket, j_bucket)
        pad = n_rows - n_active
        qidx += [0] * pad
        src += [0] * pad
        lab += [0] * pad
        dst += [0] * pad
        start += [False] * pad
        return BatchedTransitionTable(
            qidx=jnp.asarray(np.array(qidx, np.int32)),
            src=jnp.asarray(np.array(src, np.int32)),
            lab=jnp.asarray(np.array(lab, np.int32)),
            dst=jnp.asarray(np.array(dst, np.int32)),
            start_mask=jnp.asarray(np.array(start, bool)),
            active=jnp.asarray(np.array([True] * n_active + [False] * pad)),
            n_queries=len(dfas),
            k=k_max,
            n_labels=max(len(labels), 1),
        )


def batched_relax_round(
    dist: jnp.ndarray,          # (Q, N, N, K) in the backend's representation
    adj: jnp.ndarray,           # (L, N, N) shared adjacency (same repr)
    btt: BatchedTransitionTable,
    backend: BackendLike = "jnp",
    query_mask: Optional[jnp.ndarray] = None,   # (Q,) bool, True = relax
) -> jnp.ndarray:
    """One relaxation round over ALL queries' transitions at once.

    ``query_mask`` is the per-query convergence mask: rows owned by a masked
    (False) query contribute the semiring zero and the query's dist slices
    pass through untouched, so an already-converged (or inert padding) lane
    stops participating in the round instead of relaxing as a no-op.
    Transitions only ever read their OWN query's dist slices, so masking one
    lane cannot perturb another (the soundness condition for early per-query
    convergence in :func:`batched_closure`). Note the dense round is
    shape-static: masked rows are still contracted, then zeroed — the mask
    buys exact per-query round accounting (and, on a Q-sharded deployment,
    the signal to skip a converged lane's contraction entirely), not fewer
    FLOPs on a single device."""
    backend = resolve_backend(backend)
    q, n, _, k = dist.shape
    active = btt.active
    if query_mask is not None:
        active = jnp.logical_and(active, query_mask[btt.qidx])
    # contraction (masked rows carry the semiring zero already); the adj
    # operand's LAYOUT dispatches at trace time — an EllAdjacency is a
    # different pytree, so the jitted callers key separate traces and the
    # Python isinstance is resolved once per compile, never per step
    if isinstance(adj, EllAdjacency):
        contrib = backend.contract_batched_ell(dist, adj, btt, active)
        a_l = ell_label_rows(adj, btt.lab, backend.zero)  # (J, N, N)
    else:
        contrib = backend.contract_batched(dist, adj, btt, active)  # (J, N, N)
        a_l = adj[btt.lab]                            # (J, N, N) [u, v]
    # base term: seed (x, x, s0) = +inf => min(+inf, adj[l, x, v]) = adj
    # (applied only to ACTIVE start rows so it cannot unmask a zeroed row)
    base_rows = jnp.logical_and(btt.start_mask, active)
    contrib = jnp.where(base_rows[:, None, None],
                        jnp.maximum(contrib, a_l), contrib)
    # scatter-max into (query, dst-state) slices; empty segments fill the
    # dtype minimum (below the semiring zero in every representation)
    seg = btt.qidx * k + btt.dst                      # (J,)
    scat = jax.ops.segment_max(contrib, seg, num_segments=q * k)
    upd = jnp.transpose(scat.reshape(q, k, n, n), (0, 2, 3, 1))
    out = jnp.maximum(dist, upd)
    if query_mask is not None:
        out = jnp.where(query_mask[:, None, None, None], out, dist)
    return out


def _masked_closure_loop(
    dist_op: jnp.ndarray,
    adj_op: jnp.ndarray,
    btt: BatchedTransitionTable,
    backend: ContractionBackend,
    mask0: jnp.ndarray,
    bound: int,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """The convergence-masked fixpoint loop on operands ALREADY in the
    backend's representation (shared by :func:`batched_closure` and the
    frontier path's overflow fallback — the fallback must run the exact
    dense loop so a fallback dispatch stays bit-identical to ``frontier="off"``)."""

    def cond(carry):
        _d, mask, it, _qr = carry
        return jnp.logical_and(jnp.any(mask), it < bound)

    def body(carry):
        d, mask, it, qr = carry
        nd = batched_relax_round(d, adj_op, btt, backend, query_mask=mask)
        changed = jnp.any(nd > d, axis=(1, 2, 3))     # (Q,) per-query
        return nd, jnp.logical_and(mask, changed), it + 1, qr + mask

    dist0 = batched_relax_round(dist_op, adj_op, btt, backend, query_mask=mask0)
    changed0 = jnp.logical_and(mask0, jnp.any(dist0 > dist_op, axis=(1, 2, 3)))
    qr0 = mask0.astype(jnp.int32)
    dist_f, _, rounds, query_rounds = jax.lax.while_loop(
        cond, body, (dist0, changed0, jnp.asarray(1, jnp.int32), qr0)
    )
    return dist_f, rounds, query_rounds


def batched_closure(
    dist: jnp.ndarray,
    adj: jnp.ndarray,
    btt: BatchedTransitionTable,
    backend: BackendLike = "jnp",
    max_rounds: int = 0,
    query_mask: Optional[jnp.ndarray] = None,   # (Q,) bool initial mask
    now: Optional[jnp.ndarray] = None,          # () stream clock
    w_max: Optional[jnp.ndarray] = None,        # () group's largest window
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Iterate batched relaxation with per-query convergence masking.

    Each round relaxes only the queries still changing: once a query's round
    produces no update it is at its fixpoint (its transitions read only its
    own slices and the shared adjacency, which is constant during the
    closure), so it is masked out of every subsequent round. The loop ends
    when the slowest query converges.

    ``query_mask`` optionally restricts which queries participate at all
    (inert padding lanes of a live engine, or a single lane being seeded at
    registration); masked-from-the-start queries count zero rounds.

    Returns ``(dist, rounds, query_rounds)``: ``rounds`` is the global
    iteration count (max over participating queries; identical to the
    unmasked regime — the loop still runs until the slowest member
    settles), ``query_rounds`` is the (Q,) int32 per-query count of rounds
    the query actively relaxed. ``query_rounds.sum()`` vs Q * ``rounds``
    (benchmarks/fig12_multi_query.py) quantifies how much of the group's
    relaxation is no-op tail a Q-sharded execution could skip.

    ``now``/``w_max`` (the stream clock and the group's largest window)
    anchor backends whose operand representation moves with the clock:
    ``prepare_state`` converts the f32 timestamp arrays once at entry,
    every round runs in the backend's representation, ``decode_state``
    converts back once at exit (identity for jnp/pallas).

    A :class:`~repro.core.sparse_dist.RowSparseDist` ``dist`` takes the
    dense-superset round trip: densify, run the identical dense loop,
    re-pack (the non-frontier dispatches — query registration, relax —
    are whole-state fixpoints anyway; only the frontier paths have a
    row-local form worth keeping sparse end-to-end)."""
    if isinstance(dist, RowSparseDist):
        dense, rounds, qrounds = batched_closure(
            rsd_to_dense(dist), adj, btt, backend, max_rounds,
            query_mask, now, w_max)
        return (rsd_from_dense(dense, dist.dist_cap, dist.ovf_cap,
                               dist.lost), rounds, qrounds)
    backend = resolve_backend(backend)
    q, n, _, k = dist.shape
    bound = max_rounds if max_rounds > 0 else n * k + 1
    mask0 = (jnp.ones((q,), bool) if query_mask is None
             else jnp.asarray(query_mask, bool))
    dist_op, adj_op = backend.prepare_state(dist, adj, now, w_max)
    dist_f, rounds, query_rounds = _masked_closure_loop(
        dist_op, adj_op, btt, backend, mask0, bound)
    return backend.decode_state(dist_f, now, w_max), rounds, query_rounds


def batched_valid_pairs(
    dist: jnp.ndarray, finals: jnp.ndarray, low: jnp.ndarray
) -> jnp.ndarray:
    """(Q, N, N) bool validity per query: finals is (Q, K), low is (Q,)
    (per-query window thresholds applied at read time).

    A :class:`~repro.core.sparse_dist.RowSparseDist` ``dist`` routes to
    the sparse emit (:func:`~repro.core.sparse_dist.rsd_valid_pairs`):
    only stored entries are reduced — O(Q·N·C) instead of the dense
    O(Q·N²·K) scan that dominates per-event cost at large N."""
    with jax.named_scope("batched_valid_pairs"):
        if isinstance(dist, RowSparseDist):
            return rsd_valid_pairs(dist, finals, low)
        acc = jnp.where(finals[:, None, None, :], dist, NEG_INF)
        best = jnp.max(acc, axis=3)
        return best > low[:, None, None]


# ---------------------------------------------------------------------------
# Frontier-restricted relaxation (PR 5 tentpole)
#
# The dense round contracts ALL N source rows of every lane even when a
# micro-batch of B inserted edges can only perturb a few of them. But the
# (max, min) recurrence couples dist[q, x, v, t] only to dist[q, x, u, s] —
# the SAME source row x — so each row evolves independently given the shared
# adjacency, and a closure that was at fixpoint before the batch can only
# change on rows that either start at an inserted edge's source (the base
# term) or already reach one with a finite entry (any longer path through a
# new edge factors as x →* u → v, and the x →* u prefix is recorded at the
# pre-batch fixpoint). Those DIRTY rows are an O(Q·N²·K) elementwise
# reduction to find — cheap next to the O(J·N³) contraction they avoid —
# and a round restricted to them reaches the exact dense fixpoint: clean
# rows are provably stable (their round-1 update is a no-op), and dirty
# rows see the same contributions they would in the dense round.
#
# F (the frontier capacity) is a trace-time shape, bucketed ×2 by the
# executor so compile caches are reused; when the live frontier overflows F
# the dispatch falls back to the dense loop IN-DISPATCH (lax.cond) — sound
# and bit-identical, since the dense round is a superset — so worst-case
# cost never exceeds the dense path. Rows that stop changing are masked out
# (never re-added: a row's fate depends only on itself), so the frontier
# only shrinks across rounds and per-event work is O(R·J·F·N²).
# ---------------------------------------------------------------------------


class FrontierStats(NamedTuple):
    """Per-dispatch frontier telemetry (device scalars; the executor queues
    them with the round counters and converts lazily)."""

    seed_rows: jnp.ndarray      # () int32 dirty rows across all lanes
    max_lane_rows: jnp.ndarray  # () int32 largest single-lane frontier
    rows_relaxed: jnp.ndarray   # () int32 sum over rounds of rows relaxed
    fell_back: jnp.ndarray      # () bool dense fallback taken (overflow)


def frontier_seed(
    dist: jnp.ndarray,          # (Q, N, N, K) f32 timestamps (pre-encode)
    src: jnp.ndarray,           # (B,) int32 inserted-edge source slots
    smask: jnp.ndarray,         # (B,) bool batch padding mask
    query_mask: Optional[jnp.ndarray] = None,   # (Q,) bool live lanes
) -> jnp.ndarray:
    """(Q, N) bool dirty-row mask for a batch of inserted edges: rows
    x = src (base term) plus rows with a finite entry reaching an inserted
    edge's source in any DFA state. Computed on the RAW f32 timestamps
    (finite = ``> -inf``), which is exact for the float backends and a
    conservative superset for clock-anchored representations (an ancient
    finite timestamp encodes to the bucket zero; relaxing its row is then a
    no-op, never an error)."""
    q, n, _, k = dist.shape
    with jax.named_scope("frontier_seed"):
        idx = jnp.where(smask, src, n)     # out-of-range -> dropped
        src_mask = jnp.zeros((n,), bool).at[idx].set(True, mode="drop")
        reach = jnp.any(
            jnp.logical_and(dist > NEG_INF, src_mask[None, None, :, None]),
            axis=(2, 3),
        )                                   # (Q, N) rows reaching a batch source
        dirty = jnp.logical_or(reach, src_mask[None, :])
        if query_mask is not None:
            dirty = jnp.logical_and(dirty, query_mask[:, None])
    return dirty


def frontier_seed_gathered(
    dist: jnp.ndarray,          # (Q, N, N, K) f32 timestamps (pre-encode)
    src: jnp.ndarray,           # (B,) int32 inserted-edge source slots
    smask: jnp.ndarray,         # (B,) bool batch padding mask
    query_mask: Optional[jnp.ndarray] = None,   # (Q,) bool live lanes
) -> jnp.ndarray:
    """:func:`frontier_seed` with the O(N²) scan replaced by a gather.

    The dense seed tests EVERY dist column against a scattered (N,) source
    mask — O(Q·N²·K) reads per event, the term that dominates once the
    relaxation itself is frontier-restricted. But the batch names its
    sources outright, so gathering the B columns ``dist[:, :, src, :]``
    and reducing over (B, K) reads O(Q·N·B·K) — the seed cost scales with
    the batch, not the graph. Duplicated sources in the batch are benign
    (``any`` folds them), masked slots are excluded explicitly, and the
    result is EXACTLY the dense seed's mask: both reduce the same set of
    columns. Used by the ELL layout (whose whole point is breaking the
    O(N²) wall); the dense layout keeps the scan so its dispatch shapes
    and telemetry stay byte-stable."""
    q, n, _, k = dist.shape
    with jax.named_scope("frontier_seed"):
        cols = dist[:, :, jnp.where(smask, src, 0), :]       # (Q, N, B, K)
        reach = jnp.any(
            jnp.logical_and(cols > NEG_INF, smask[None, None, :, None]),
            axis=(2, 3),
        )                                   # (Q, N) rows reaching a batch source
        idx = jnp.where(smask, src, n)
        src_mask = jnp.zeros((n,), bool).at[idx].set(True, mode="drop")
        dirty = jnp.logical_or(reach, src_mask[None, :])
        if query_mask is not None:
            dirty = jnp.logical_and(dirty, query_mask[:, None])
    return dirty


def pack_frontier(
    dirty: jnp.ndarray, f_cap: int
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Compact a (Q, N) dirty mask into per-lane row indices.

    Returns ``(rows, rowmask, counts)``: rows (Q, F) int32 (first
    ``min(count, F)`` slots hold the dirty row ids in ascending order,
    padding is 0 — harmless: padded slots are masked and a masked slot's
    contribution is the semiring zero), rowmask (Q, F) bool, counts (Q,)
    int32 of TRUE dirty rows (counts > F signals overflow; the overflowing
    rows are dropped here, which is why callers must take the dense
    fallback in that case)."""
    q, n = dirty.shape
    with jax.named_scope("pack_frontier"):
        cnt = jnp.sum(dirty, axis=1).astype(jnp.int32)
        pos = jnp.cumsum(dirty, axis=1) - 1                  # (Q, N)
        pos = jnp.where(dirty, jnp.minimum(pos, f_cap), f_cap)
        rows = jnp.zeros((q, f_cap), jnp.int32).at[
            jnp.arange(q)[:, None], pos
        ].set(jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32)[None, :],
                               (q, n)), mode="drop")
        rowmask = jnp.arange(f_cap)[None, :] < jnp.minimum(cnt, f_cap)[:, None]
    return rows, rowmask, cnt


def frontier_relax_round(
    dist: jnp.ndarray,          # (Q, N, N, K) in the backend's representation
    adj: jnp.ndarray,           # (L, N, N) shared adjacency (same repr)
    btt: BatchedTransitionTable,
    backend: BackendLike,
    rows: jnp.ndarray,          # (Q, F) int32 frontier row indices
    rowmask: jnp.ndarray,       # (Q, F) bool valid-slot mask
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One relaxation round restricted to the frontier rows.

    Gathers the (Q, F, N, K) slab of dirty source rows, contracts it
    against the shared adjacency through the backend's ``contract_rows``
    hook (the same substrate the dense round uses — pallas/bucket kernels
    see a skinny (F, N) operand), applies the base term at the frontier
    rows, scatter-maxes the slab back, and reports which slots changed.
    Returns ``(dist', changed)`` with changed (Q, F) already intersected
    with ``rowmask`` — the next round's mask (a row whose round produced no
    update is at its fixpoint forever: it depends only on itself)."""
    backend = resolve_backend(backend)
    q = dist.shape[0]
    lane = jnp.arange(q)[:, None]
    slab = dist[lane, rows]                            # (Q, F, N, K)
    new_slab, changed = _frontier_slab_round(slab, adj, btt, backend,
                                             rows, rowmask)
    out = dist.at[lane, rows].max(new_slab)
    return out, changed


def _frontier_slab_round(
    slab: jnp.ndarray,          # (Q, F, N, K) gathered frontier rows
    adj: jnp.ndarray,           # (L, N, N) shared adjacency (same repr)
    btt: BatchedTransitionTable,
    backend: ContractionBackend,
    rows: jnp.ndarray,          # (Q, F) int32 frontier row indices
    rowmask: jnp.ndarray,       # (Q, F) bool valid-slot mask
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One frontier round on the gathered slab itself (no scatter back).

    The (max, min) recurrence couples a source row only to ITSELF and the
    shared adjacency, and the frontier only shrinks, so a round never
    needs to read a row outside the slab: keeping the whole round loop
    slab-local is bit-identical to re-gathering from ``dist`` each round
    (valid rows are unique per lane — ``pack_frontier`` packs a mask —
    and padded slots are masked to zero contribution). The dense-layout
    :func:`frontier_relax_round` wraps this with its per-round
    gather/scatter-max; the row-sparse layout gathers ONCE, loops here,
    and scatters once at the end of the dispatch."""
    q, f, n, k = slab.shape
    zero = jnp.asarray(backend.zero, slab.dtype)
    slab_s = slab[btt.qidx, :, :, btt.src]             # (J, F, N) [f, u]
    rows_j = rows[btt.qidx]                            # (J, F)
    if isinstance(adj, EllAdjacency):
        # gather-contract straight off the ELL rows: O(F·N·E) per
        # transition, and the base term densifies ONLY the F frontier rows
        # — nothing O(N²) is materialized on this path
        contrib = backend.contract_rows_ell(slab_s, adj, btt.lab)
        a_base = ell_rows_dense(adj, btt.lab, rows_j, backend.zero)
    else:
        a_l = adj[btt.lab]                             # (J, N, N) [u, v]
        contrib = backend.contract_rows(slab_s, a_l)   # (J, F, N) [f, v]
        # base term at the frontier rows: adj[l, x, v] for x = rows[q, f]
        a_base = jnp.take_along_axis(a_l, rows_j[:, :, None], axis=1)
    base_rows = jnp.logical_and(btt.start_mask, btt.active)
    contrib = jnp.where(base_rows[:, None, None],
                        jnp.maximum(contrib, a_base), contrib)
    # zero inactive transition rows and invalid/converged frontier slots
    act = jnp.logical_and(btt.active[:, None], rowmask[btt.qidx])  # (J, F)
    contrib = jnp.where(act[:, :, None], contrib, zero)
    seg = btt.qidx * k + btt.dst
    scat = jax.ops.segment_max(contrib, seg, num_segments=q * k)  # (QK, F, N)
    upd = jnp.transpose(scat.reshape(q, k, f, n), (0, 2, 3, 1))   # (Q, F, N, K)
    new_slab = jnp.maximum(slab, upd)
    changed = jnp.logical_and(
        jnp.any(new_slab > slab, axis=(2, 3)), rowmask)
    return new_slab, changed


def frontier_closure(
    dist: jnp.ndarray,
    adj: jnp.ndarray,
    btt: BatchedTransitionTable,
    backend: BackendLike,
    src: jnp.ndarray,           # (B,) int32 inserted-edge source slots
    smask: jnp.ndarray,         # (B,) bool batch padding mask
    f_cap: int,                 # trace-time frontier capacity (bucketed ×2)
    query_mask: Optional[jnp.ndarray] = None,
    max_rounds: int = 0,
    now: Optional[jnp.ndarray] = None,
    w_max: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, FrontierStats]:
    """Frontier-restricted closure with in-dispatch dense fallback.

    Seeds the frontier from the batch itself (see :func:`frontier_seed`),
    iterates frontier rounds until every row settles, and — when any
    lane's dirty set overflows ``f_cap`` — runs the exact dense masked
    loop instead (``lax.cond``: both branches are traced, the choice is a
    runtime bit, so there is no recompile storm on overflow). Results are
    bit-identical to :func:`batched_closure` either way.

    Returns ``(dist, rounds, query_rounds, stats)``. ``query_rounds``
    counts rounds a lane had a non-empty frontier — a live lane the batch
    never dirtied counts ZERO rounds here (the dense loop charges every
    live lane its round-1 no-op), which is exactly the per-event work
    decoupling the frontier buys."""
    if isinstance(dist, RowSparseDist):
        return _rowsparse_frontier_closure(
            dist, adj, btt, backend, src, smask, f_cap,
            query_mask=query_mask, max_rounds=max_rounds,
            now=now, w_max=w_max)
    backend = resolve_backend(backend)
    q, n, _, k = dist.shape
    bound = max_rounds if max_rounds > 0 else n * k + 1
    mask0 = (jnp.ones((q,), bool) if query_mask is None
             else jnp.asarray(query_mask, bool))
    # ELL dispatches seed via the batch-column gather (O(Q·N·B·K), the
    # representation's headline win); dense keeps the scan — same mask
    # either way (frontier_seed_gathered docstring), so results and the
    # overflow decision are layout-independent
    seed_fn = (frontier_seed_gathered if isinstance(adj, EllAdjacency)
               else frontier_seed)
    dirty = seed_fn(dist, src, smask, mask0)
    rows, rowmask0, cnt = pack_frontier(dirty, f_cap)
    seed_rows = jnp.sum(cnt)
    max_lane_rows = jnp.max(cnt)
    overflow = jnp.any(cnt > f_cap)
    dist_op, adj_op = backend.prepare_state(dist, adj, now, w_max)

    @jax.named_scope("dense_fallback")
    def dense_branch(_):
        d_f, rounds, qrounds = _masked_closure_loop(
            dist_op, adj_op, btt, backend, mask0, bound)
        live_rows = jnp.sum(mask0.astype(jnp.int32)) * n
        return d_f, rounds, qrounds, rounds * live_rows

    def frontier_branch(_):
        def cond(carry):
            _d, rm, it, _qr, _rr = carry
            return jnp.logical_and(jnp.any(rm), it < bound)

        @jax.named_scope("frontier_round")
        def body(carry):
            d, rm, it, qr, rr = carry
            nd, changed = frontier_relax_round(d, adj_op, btt, backend,
                                               rows, rm)
            qactive = jnp.any(rm, axis=1).astype(jnp.int32)
            return (nd, changed, it + 1, qr + qactive,
                    rr + jnp.sum(rm.astype(jnp.int32)))

        d_f, _, rounds, qrounds, rr = jax.lax.while_loop(
            cond, body,
            (dist_op, rowmask0, jnp.asarray(0, jnp.int32),
             jnp.zeros((q,), jnp.int32), jnp.asarray(0, jnp.int32)))
        return d_f, rounds, qrounds, rr

    dist_f, rounds, qrounds, rows_relaxed = jax.lax.cond(
        overflow, dense_branch, frontier_branch, None)
    stats = FrontierStats(seed_rows, max_lane_rows, rows_relaxed, overflow)
    return backend.decode_state(dist_f, now, w_max), rounds, qrounds, stats


# ---------------------------------------------------------------------------
# Frontier-restricted DELETION (PR 6 tentpole)
#
# A deleted edge (u, v, l) can only invalidate derivations whose path passes
# through it — and every such path factors as x →* u → v →* ·, where the
# x →* u prefix is recorded at the PRE-delete fixpoint as a finite
# dist[q, x, u, s] entry (the length-0 prefix x = u is the base-term case).
# So the set of rows whose value can change is EXACTLY the reachability test
# `frontier_seed` already runs for inserts, evaluated against the pre-delete
# state: the deleted edge's *cone*. Rows outside the cone keep their
# pre-delete values, which remain exact fixpoints of the retained adjacency
# (their contraction term at u' = u reads dist[x, u, s] = -inf and the base
# term requires x = u — both excluded by cone membership), while cone rows
# are cleared to the semiring zero and re-derived from scratch over the
# retained adjacency: round 1 re-applies their base terms (`a_base` in
# `frontier_relax_round`), later rounds propagate, and monotone convergence
# lands each row on the least fixpoint — the same value a dense
# from-scratch re-closure computes, so the overflow fallback (which IS the
# dense from-scratch loop) is bit-identical by construction.
#
# One caveat on RAW-array identity: rows outside the cone keep their stored
# values VERBATIM, including window-dead entries whose supporting edges have
# already been expired out of the adjacency (expiry is lazy and never
# touches dist). A dense from-scratch delete garbage-collects those as a
# side effect. The two states agree on every entry above the window
# threshold — an entry > now - w has its best witnessing path fully
# retained (expiry only evicts edges <= the monotone threshold), so the
# stored value equals the retained adjacency's least fixpoint there, and a
# dead entry can never resurface (bottlenecks only age, the threshold only
# rises). Emitted results, invalidation sets, and every thresholded read
# are therefore identical; only the unobservable dead entries may differ.
# ---------------------------------------------------------------------------


def delete_cone(
    dist: jnp.ndarray,          # (Q, N, N, K) PRE-delete f32 timestamps
    src: jnp.ndarray,           # (B,) int32 deleted-edge source slots
    smask: jnp.ndarray,         # (B,) bool batch padding mask
    query_mask: Optional[jnp.ndarray] = None,   # (Q,) bool live lanes
) -> jnp.ndarray:
    """(Q, N) bool invalidation cone of a batch of deleted edges: rows x
    whose pre-delete ``dist[q, x, :, :]`` has a finite entry reaching a
    deleted edge's source u in any DFA state, plus the rows x = u
    themselves (base-term derivations). This is the same reduction as
    :func:`frontier_seed` — for inserts it bounds where new derivations can
    APPEAR, for deletes (run against the pre-delete state) it bounds where
    existing derivations can have PASSED THROUGH the dropped edge — so the
    two paths share one implementation and one cost: O(Q·N²·K)
    elementwise."""
    return frontier_seed(dist, src, smask, query_mask)


def frontier_delete(
    dist: jnp.ndarray,          # (Q, N, N, K) PRE-delete state
    adj: jnp.ndarray,           # (L, N, N) RETAINED adjacency (edge dropped)
    btt: BatchedTransitionTable,
    backend: BackendLike,
    src: jnp.ndarray,           # (B,) int32 deleted-edge source slots
    smask: jnp.ndarray,         # (B,) bool batch padding mask
    f_cap: int,
    query_mask: Optional[jnp.ndarray] = None,
    max_rounds: int = 0,
    now: Optional[jnp.ndarray] = None,
    w_max: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, FrontierStats]:
    """Cone-seeded incremental re-derivation after a batch of deletions.

    Computes the deleted edges' cone on the pre-delete ``dist``, clears
    exactly those rows to the semiring zero, and re-derives them with the
    same frontier round loop ingest uses — rows outside the cone are
    untouched (they are already at the retained adjacency's fixpoint on
    every window-valid entry; see the section comment for the argument and
    for the one place raw arrays may differ — window-dead entries in clean
    rows). On cone overflow the dispatch falls back IN-DISPATCH to the
    dense from-scratch re-closure (all rows cleared), which is the exact
    computation the non-frontier delete path runs — observable results are
    identical either way.

    Returns ``(dist, rounds, query_rounds, stats)`` with the same contract
    as :func:`frontier_closure`."""
    if isinstance(dist, RowSparseDist):
        return _rowsparse_frontier_delete(
            dist, adj, btt, backend, src, smask, f_cap,
            query_mask=query_mask, max_rounds=max_rounds,
            now=now, w_max=w_max)
    backend = resolve_backend(backend)
    q, n, _, k = dist.shape
    bound = max_rounds if max_rounds > 0 else n * k + 1
    mask0 = (jnp.ones((q,), bool) if query_mask is None
             else jnp.asarray(query_mask, bool))
    # same layout split as frontier_closure: the cone IS the seed reduction
    cone_fn = (frontier_seed_gathered if isinstance(adj, EllAdjacency)
               else delete_cone)
    dirty = cone_fn(dist, src, smask, mask0)
    rows, rowmask0, cnt = pack_frontier(dirty, f_cap)
    seed_rows = jnp.sum(cnt)
    max_lane_rows = jnp.max(cnt)
    overflow = jnp.any(cnt > f_cap)
    cleared = jnp.where(dirty[:, :, None, None], NEG_INF, dist)
    dist_op, adj_op = backend.prepare_state(cleared, adj, now, w_max)

    @jax.named_scope("dense_fallback")
    def dense_branch(_):
        # from-scratch over ALL rows — exactly what the non-frontier delete
        # dispatch runs, so a fallback stays bit-identical to frontier="off"
        d0 = backend.encode(jnp.full_like(dist, NEG_INF), now, w_max)
        d_f, rounds, qrounds = _masked_closure_loop(
            d0, adj_op, btt, backend, mask0, bound)
        live_rows = jnp.sum(mask0.astype(jnp.int32)) * n
        return d_f, rounds, qrounds, rounds * live_rows

    def frontier_branch(_):
        def cond(carry):
            _d, rm, it, _qr, _rr = carry
            return jnp.logical_and(jnp.any(rm), it < bound)

        @jax.named_scope("frontier_round")
        def body(carry):
            d, rm, it, qr, rr = carry
            nd, changed = frontier_relax_round(d, adj_op, btt, backend,
                                               rows, rm)
            qactive = jnp.any(rm, axis=1).astype(jnp.int32)
            return (nd, changed, it + 1, qr + qactive,
                    rr + jnp.sum(rm.astype(jnp.int32)))

        d_f, _, rounds, qrounds, rr = jax.lax.while_loop(
            cond, body,
            (dist_op, rowmask0, jnp.asarray(0, jnp.int32),
             jnp.zeros((q,), jnp.int32), jnp.asarray(0, jnp.int32)))
        return d_f, rounds, qrounds, rr

    dist_f, rounds, qrounds, rows_relaxed = jax.lax.cond(
        overflow, dense_branch, frontier_branch, None)
    stats = FrontierStats(seed_rows, max_lane_rows, rows_relaxed, overflow)
    return backend.decode_state(dist_f, now, w_max), rounds, qrounds, stats


# ---------------------------------------------------------------------------
# Row-sparse dist frontier paths (PR 9 tentpole)
#
# Same closure/delete contracts as the dense-layout functions above, with
# the (Q, N, N, K) slab replaced by a RowSparseDist. The single-source row
# independence that justifies the frontier in the first place also means a
# whole DISPATCH only ever reads and writes the frontier rows — so instead
# of gathering and scattering per round, the row-sparse path densifies the
# frontier rows ONCE (the backend's gather_dist_rows kernel), runs every
# round slab-local (`_frontier_slab_round`), and scatters the finished rows
# back into the per-row sets once at the end. Backend encode/decode wraps
# the slab at the same boundary the dense path wraps the full state, so
# clock-anchored representations never leak into the stored sparse state.
#
# Overflow keeps the dense lax.cond fallback, upgraded to a round trip:
# densify -> exact dense loop -> in-jit re-pack (rsd_from_dense). Rows that
# outgrow dist_cap during the re-pack or the scatter land in the bounded
# overflow table; the executor's host-side budget drains and grows the
# capacity before the table can fill (docs/invariants.md, "the row-sparse
# overflow contract"). Results are bit-identical to the dense layout for
# the float backends; for the bucket backend identity is OBSERVABLE (same
# emitted streams) rather than raw — untouched sparse rows keep
# window-dead entries a dense round trip would garbage-collect, the same
# caveat the PR 6 delete section documents above.
# ---------------------------------------------------------------------------


def _rowsparse_frontier_closure(
    sd: RowSparseDist,
    adj,
    btt: BatchedTransitionTable,
    backend: BackendLike,
    src: jnp.ndarray,
    smask: jnp.ndarray,
    f_cap: int,
    query_mask: Optional[jnp.ndarray] = None,
    max_rounds: int = 0,
    now: Optional[jnp.ndarray] = None,
    w_max: Optional[jnp.ndarray] = None,
) -> Tuple[RowSparseDist, jnp.ndarray, jnp.ndarray, FrontierStats]:
    """:func:`frontier_closure` on a :class:`RowSparseDist` (see the
    section comment): gather-once / slab-local rounds / scatter-once,
    with the overflow fallback as a densify round trip."""
    backend = resolve_backend(backend)
    q, n, _c = sd.idx.shape
    k = sd.k
    bound = max_rounds if max_rounds > 0 else n * k + 1
    mask0 = (jnp.ones((q,), bool) if query_mask is None
             else jnp.asarray(query_mask, bool))
    # the seed walks stored entries only — same mask as the dense scan on
    # the densified state (rsd_seed_gathered docstring), so the overflow
    # decision and telemetry are layout-independent
    dirty = rsd_seed_gathered(sd, src, smask, mask0)
    rows, rowmask0, cnt = pack_frontier(dirty, f_cap)
    seed_rows = jnp.sum(cnt)
    max_lane_rows = jnp.max(cnt)
    overflow = jnp.any(cnt > f_cap)
    # encode the adjacency operand once, shared by both branches (the
    # dist operand of prepare_state is a dummy scalar: the branches
    # encode their own slab/state at their own boundary)
    _, adj_op = backend.prepare_state(
        jnp.asarray(NEG_INF, jnp.float32), adj, now, w_max)

    @jax.named_scope("dense_fallback")
    def dense_branch(_):
        d_op = backend.encode(rsd_to_dense(sd), now, w_max)
        d_f, rounds, qrounds = _masked_closure_loop(
            d_op, adj_op, btt, backend, mask0, bound)
        dense_f = backend.decode_state(d_f, now, w_max)
        out = rsd_from_dense(dense_f, sd.dist_cap, sd.ovf_cap, sd.lost)
        live_rows = jnp.sum(mask0.astype(jnp.int32)) * n
        return out, rounds, qrounds, rounds * live_rows

    def frontier_branch(_):
        with jax.named_scope("frontier_gather"):
            slab0 = rsd_gather_rows(sd, rows, backend.gather_dist_rows)
            slab_op = backend.encode(slab0, now, w_max)

        def cond(carry):
            _s, rm, it, _qr, _rr = carry
            return jnp.logical_and(jnp.any(rm), it < bound)

        @jax.named_scope("frontier_round")
        def body(carry):
            s, rm, it, qr, rr = carry
            ns, changed = _frontier_slab_round(s, adj_op, btt, backend,
                                               rows, rm)
            qactive = jnp.any(rm, axis=1).astype(jnp.int32)
            return (ns, changed, it + 1, qr + qactive,
                    rr + jnp.sum(rm.astype(jnp.int32)))

        s_f, _, rounds, qrounds, rr = jax.lax.while_loop(
            cond, body,
            (slab_op, rowmask0, jnp.asarray(0, jnp.int32),
             jnp.zeros((q,), jnp.int32), jnp.asarray(0, jnp.int32)))
        with jax.named_scope("frontier_scatter"):
            slab_f = backend.decode_state(s_f, now, w_max)
            out = rsd_scatter_rows(sd, rows, rowmask0, slab_f)
        return out, rounds, qrounds, rr

    out, rounds, qrounds, rows_relaxed = jax.lax.cond(
        overflow, dense_branch, frontier_branch, None)
    stats = FrontierStats(seed_rows, max_lane_rows, rows_relaxed, overflow)
    return out, rounds, qrounds, stats


def _rowsparse_frontier_delete(
    sd: RowSparseDist,
    adj,
    btt: BatchedTransitionTable,
    backend: BackendLike,
    src: jnp.ndarray,
    smask: jnp.ndarray,
    f_cap: int,
    query_mask: Optional[jnp.ndarray] = None,
    max_rounds: int = 0,
    now: Optional[jnp.ndarray] = None,
    w_max: Optional[jnp.ndarray] = None,
) -> Tuple[RowSparseDist, jnp.ndarray, jnp.ndarray, FrontierStats]:
    """:func:`frontier_delete` on a :class:`RowSparseDist`: the cone is
    seeded from the stored entries of the PRE-delete state, cone rows
    re-derive from a zeroed slab (clearing + re-deriving in one scatter:
    the final scatter's full-row overwrite IS the clear — exact even for
    rows that shrink), non-cone rows are never touched."""
    backend = resolve_backend(backend)
    q, n, _c = sd.idx.shape
    k = sd.k
    bound = max_rounds if max_rounds > 0 else n * k + 1
    mask0 = (jnp.ones((q,), bool) if query_mask is None
             else jnp.asarray(query_mask, bool))
    dirty = rsd_seed_gathered(sd, src, smask, mask0)
    rows, rowmask0, cnt = pack_frontier(dirty, f_cap)
    seed_rows = jnp.sum(cnt)
    max_lane_rows = jnp.max(cnt)
    overflow = jnp.any(cnt > f_cap)
    _, adj_op = backend.prepare_state(
        jnp.asarray(NEG_INF, jnp.float32), adj, now, w_max)

    @jax.named_scope("dense_fallback")
    def dense_branch(_):
        # from-scratch over ALL rows — exactly the non-frontier delete
        # computation, re-packed in-jit on the way out
        d0 = backend.encode(
            jnp.full((q, n, n, k), NEG_INF, jnp.float32), now, w_max)
        d_f, rounds, qrounds = _masked_closure_loop(
            d0, adj_op, btt, backend, mask0, bound)
        dense_f = backend.decode_state(d_f, now, w_max)
        out = rsd_from_dense(dense_f, sd.dist_cap, sd.ovf_cap, sd.lost)
        live_rows = jnp.sum(mask0.astype(jnp.int32)) * n
        return out, rounds, qrounds, rounds * live_rows

    def frontier_branch(_):
        # cone rows start at the semiring zero (re-derivation from
        # scratch); rounds only read slab rows, so no gather is needed
        slab0 = backend.encode(
            jnp.full((q, f_cap, n, k), NEG_INF, jnp.float32), now, w_max)

        def cond(carry):
            _s, rm, it, _qr, _rr = carry
            return jnp.logical_and(jnp.any(rm), it < bound)

        @jax.named_scope("frontier_round")
        def body(carry):
            s, rm, it, qr, rr = carry
            ns, changed = _frontier_slab_round(s, adj_op, btt, backend,
                                               rows, rm)
            qactive = jnp.any(rm, axis=1).astype(jnp.int32)
            return (ns, changed, it + 1, qr + qactive,
                    rr + jnp.sum(rm.astype(jnp.int32)))

        s_f, _, rounds, qrounds, rr = jax.lax.while_loop(
            cond, body,
            (slab0, rowmask0, jnp.asarray(0, jnp.int32),
             jnp.zeros((q,), jnp.int32), jnp.asarray(0, jnp.int32)))
        with jax.named_scope("frontier_scatter"):
            slab_f = backend.decode_state(s_f, now, w_max)
            out = rsd_scatter_rows(sd, rows, rowmask0, slab_f)
        return out, rounds, qrounds, rr

    out, rounds, qrounds, rows_relaxed = jax.lax.cond(
        overflow, dense_branch, frontier_branch, None)
    stats = FrontierStats(seed_rows, max_lane_rows, rows_relaxed, overflow)
    return out, rounds, qrounds, stats


# ---------------------------------------------------------------------------
# Sharded (shard_map-local) round variants
#
# The mesh executor (distributed/executor.py) shards the Q lane axis over
# the mesh's data axis and (optionally) the vertex axis over model. Inside
# a shard_map block each shard sees dist (Q_l, N, N_m, K) plus ONLY its own
# queries' transition rows, relaxes them to ITS OWN fixpoint, and skips the
# contraction entirely once its lanes have all converged — the realized form
# of the per-query convergence masking that the dense single-device round
# could only account for (batched_relax_round docstring). The row layout is
# built host-side by `shard_transitions`.
# ---------------------------------------------------------------------------


def shard_transitions(
    btt: BatchedTransitionTable, q_cap: int, n_shards: int, j_bucket: int = 8
) -> Tuple[jnp.ndarray, ...]:
    """Regroup a flattened transition table by lane shard.

    Lanes are block-partitioned: shard i owns lanes [i*q_cap/n_shards,
    (i+1)*q_cap/n_shards). Returns six (n_shards, J_s) arrays — qidx
    (SHARD-LOCAL lane index), src, lab, dst, start_mask, active — with J_s
    the bucketed max row count over shards (padding rows inert). ``q_cap``
    must be a multiple of ``n_shards`` (the engine rounds lane capacity to
    the executor's ``q_multiple``).
    """
    if q_cap % n_shards:
        raise ValueError(f"q_cap {q_cap} not divisible by {n_shards} shards")
    q_shard = q_cap // n_shards
    qidx = np.asarray(btt.qidx)
    active = np.asarray(btt.active)
    src = np.asarray(btt.src)
    lab = np.asarray(btt.lab)
    dst = np.asarray(btt.dst)
    start = np.asarray(btt.start_mask)
    rows: List[List[int]] = [[] for _ in range(n_shards)]
    for j in np.nonzero(active)[0].tolist():
        rows[int(qidx[j]) // q_shard].append(j)
    j_max = max([len(r) for r in rows] + [1])
    j_s = max(j_max + (-j_max) % j_bucket, j_bucket)
    out = {
        "qidx": np.zeros((n_shards, j_s), np.int32),
        "src": np.zeros((n_shards, j_s), np.int32),
        "lab": np.zeros((n_shards, j_s), np.int32),
        "dst": np.zeros((n_shards, j_s), np.int32),
        "start": np.zeros((n_shards, j_s), bool),
        "active": np.zeros((n_shards, j_s), bool),
    }
    for sh, row_ids in enumerate(rows):
        for jj, j in enumerate(row_ids):
            out["qidx"][sh, jj] = qidx[j] - sh * q_shard
            out["src"][sh, jj] = src[j]
            out["lab"][sh, jj] = lab[j]
            out["dst"][sh, jj] = dst[j]
            out["start"][sh, jj] = start[j]
            out["active"][sh, jj] = True
    return (jnp.asarray(out["qidx"]), jnp.asarray(out["src"]),
            jnp.asarray(out["lab"]), jnp.asarray(out["dst"]),
            jnp.asarray(out["start"]), jnp.asarray(out["active"]))


def shard_relax_round(
    dist_blk: jnp.ndarray,     # (Q_l, N, N_m, K) shard-local lane block
    adj_u: jnp.ndarray,        # (L, N_m, N) adjacency, u rows local
    adj_v: jnp.ndarray,        # (L, N, N_m) adjacency, v cols local
    qidx: jnp.ndarray,         # (J_s,) SHARD-LOCAL owning lane
    src: jnp.ndarray,          # (J_s,)
    lab: jnp.ndarray,          # (J_s,)
    dst: jnp.ndarray,          # (J_s,)
    start_mask: jnp.ndarray,   # (J_s,)
    active: jnp.ndarray,       # (J_s,)
    query_mask: jnp.ndarray,   # (Q_l,) bool, True = relax
    backend: BackendLike = "jnp",
    model_axis: Optional[str] = None,
    model_size: int = 1,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One relaxation round on one lane shard (shard_map-local).

    The u-contraction runs over the shard's LOCAL u-block; when the vertex
    axis is sharded (``model_size > 1``) the per-block partials are
    max-combined across ``model_axis`` (exact: max is associative) and the
    shard keeps its v-column block. Returns ``(new_dist_blk, changed)``
    with ``changed`` (Q_l,) synchronized across the model axis so every
    peer of a lane shard agrees on convergence (uniform loop trip counts —
    the condition that makes collectives inside the closure loop safe).

    Masking semantics mirror :func:`batched_relax_round` exactly: masked
    lanes contribute the semiring zero and pass through untouched.
    Operands are in the backend's representation (:func:`shard_closure`
    converts at the dispatch boundary).
    """
    backend = resolve_backend(backend)
    q_l, n, n_m, k = dist_blk.shape
    act = jnp.logical_and(active, query_mask[qidx])
    d_s = dist_blk[qidx, :, :, src]               # (J, N, N_m) [x, u_local]
    a_u = adj_u[lab]                              # (J, N_m, N) [u_local, v]
    part = backend.contract_rows(d_s, a_u)        # (J, N, N)   [x, v] partial
    if model_axis is not None and model_size > 1:
        part = jax.lax.pmax(part, model_axis)
        vstart = jax.lax.axis_index(model_axis) * n_m
        contrib = jax.lax.dynamic_slice(
            part, (0, 0, vstart), (part.shape[0], n, n_m))
    else:
        contrib = part
    # base term: seed (x, x, s0) = +inf => min(+inf, adj[l, x, v]) = adj
    a_v = adj_v[lab]                              # (J, N, N_m)
    contrib = jnp.where(start_mask[:, None, None],
                        jnp.maximum(contrib, a_v), contrib)
    contrib = jnp.where(act[:, None, None], contrib,
                        jnp.asarray(backend.zero, contrib.dtype))
    seg = qidx * k + dst
    scat = jax.ops.segment_max(contrib, seg, num_segments=q_l * k)
    upd = jnp.transpose(scat.reshape(q_l, k, n, n_m), (0, 2, 3, 1))
    nd = jnp.maximum(dist_blk, upd)
    nd = jnp.where(query_mask[:, None, None, None], nd, dist_blk)
    changed = jnp.any(nd > dist_blk, axis=(1, 2, 3))
    if model_axis is not None and model_size > 1:
        changed = jax.lax.pmax(changed.astype(jnp.int32), model_axis) > 0
    return nd, changed


def shard_closure(
    dist_blk: jnp.ndarray,
    adj_u: jnp.ndarray,
    adj_v: jnp.ndarray,
    rows: Tuple[jnp.ndarray, ...],   # six (J_s,) arrays (shard_transitions)
    query_mask: jnp.ndarray,         # (Q_l,) bool initial mask
    backend: BackendLike = "jnp",
    model_axis: Optional[str] = None,
    model_size: int = 1,
    max_rounds: int = 0,
    now: Optional[jnp.ndarray] = None,    # () stream clock (replicated)
    w_max: Optional[jnp.ndarray] = None,  # () group's largest window
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Shard-local closure with convergence-aware dispatch.

    A shard whose lanes are all masked (converged or inert padding) SKIPS
    the closure entirely (`lax.cond`) — zero contraction work, the win the
    single-device masked round could only account for. Otherwise the shard
    iterates to its OWN fixpoint: its loop ends when its slowest lane
    settles, independent of other shards (no cross-shard data flow — a
    transition only reads its owning lane's slices and the adjacency, which
    is constant during the closure).

    Returns ``(dist_blk, rounds, query_rounds)``: ``rounds`` () int32 is
    the rounds THIS shard actually relaxed (0 when skipped — the per-shard
    skip/finish-early signal the mesh executor aggregates into its
    masked-skip counters), ``query_rounds`` (Q_l,) matches the local
    engine's per-lane accounting.

    The backend's representation boundary sits INSIDE the run branch:
    operands are encoded once per dispatch, the loop runs on them, and the
    result decodes back to f32 timestamps. The skip branch returns the
    raw block untouched (zero work, exact passthrough). Encoding is
    elementwise and ``now`` is replicated, so the per-shard conversion is
    collective-free.
    """
    backend = resolve_backend(backend)
    qidx, src, lab, dst, start, active = rows
    q_l, n, _n_m, k = dist_blk.shape
    bound = max_rounds if max_rounds > 0 else n * k + 1

    def run(_):
        d_op = backend.encode(dist_blk, now, w_max)
        au_op = backend.encode(adj_u, now, w_max)
        av_op = backend.encode(adj_v, now, w_max)
        d_f, it_f, qr_f = _shard_dense_loop(
            d_op, au_op, av_op, rows, query_mask, backend,
            model_axis, model_size, bound)
        return backend.decode_state(d_f, now, w_max), it_f, qr_f

    def skip(_):
        return (dist_blk, jnp.asarray(0, jnp.int32),
                jnp.zeros((q_l,), jnp.int32))

    # uniform across the model peers of this lane shard (query_mask is
    # replicated along model), so collectives inside `run` stay safe
    return jax.lax.cond(jnp.any(query_mask), run, skip, None)


def _shard_dense_loop(
    d_op: jnp.ndarray,
    au_op: jnp.ndarray,
    av_op: jnp.ndarray,
    rows: Tuple[jnp.ndarray, ...],
    query_mask: jnp.ndarray,
    backend: ContractionBackend,
    model_axis: Optional[str],
    model_size: int,
    bound: int,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """The shard-local masked fixpoint loop on encoded operands (shared by
    :func:`shard_closure` and the frontier path's overflow fallback)."""
    qidx, src, lab, dst, start, active = rows

    def one_round(d, mask):
        return shard_relax_round(
            d, au_op, av_op, qidx, src, lab, dst, start, active, mask,
            backend=backend, model_axis=model_axis, model_size=model_size)

    d0, ch0 = one_round(d_op, query_mask)
    m0 = jnp.logical_and(query_mask, ch0)
    qr0 = query_mask.astype(jnp.int32)
    it0 = jnp.asarray(1, jnp.int32)

    def cond(carry):
        return carry[4]

    def body(carry):
        d, mask, it, qr, _keep = carry
        nd, ch = one_round(d, mask)
        nmask = jnp.logical_and(mask, ch)
        it = it + 1
        keep = jnp.logical_and(jnp.any(nmask), it < bound)
        return nd, nmask, it, qr + mask.astype(jnp.int32), keep

    keep0 = jnp.logical_and(jnp.any(m0), it0 < bound)
    d_f, _, it_f, qr_f, _ = jax.lax.while_loop(
        cond, body, (d0, m0, it0, qr0, keep0))
    return d_f, it_f, qr_f


def _shard_frontier_round(
    d_op: jnp.ndarray,         # (Q_l, N, N_m, K) encoded lane block
    au_op: jnp.ndarray,        # (L, N_m, N) encoded adjacency, u rows local
    av_op: jnp.ndarray,        # (L, N, N_m) encoded adjacency, v cols local
    rows: Tuple[jnp.ndarray, ...],
    frows: jnp.ndarray,        # (Q_l, F) frontier row indices (replicated
                               # across the model peers of this lane shard)
    rowmask: jnp.ndarray,      # (Q_l, F) valid-slot mask (replicated)
    backend: ContractionBackend,
    model_axis: Optional[str],
    model_size: int,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One frontier-restricted round on one lane shard: the shard-local
    form of :func:`frontier_relax_round` — the (Q_l, F, N_m, K) slab
    contracts over the LOCAL u block, partials max-combine across the
    model axis (exact), and ``changed`` is synchronized across model peers
    so the frontier mask stays uniform (the condition that keeps the
    collectives inside the closure loop safe)."""
    qidx, src, lab, dst, start, active = rows
    q_l, n, n_m, k = d_op.shape
    f = frows.shape[1]
    zero = jnp.asarray(backend.zero, d_op.dtype)
    lane = jnp.arange(q_l)[:, None]
    slab = d_op[lane, frows]                           # (Q_l, F, N_m, K)
    slab_s = slab[qidx, :, :, src]                     # (J, F, N_m) [f, u_l]
    a_u = au_op[lab]                                   # (J, N_m, N)
    part = backend.contract_rows(slab_s, a_u)          # (J, F, N) partial
    if model_axis is not None and model_size > 1:
        part = jax.lax.pmax(part, model_axis)
        vstart = jax.lax.axis_index(model_axis) * n_m
        contrib = jax.lax.dynamic_slice(
            part, (0, 0, vstart), (part.shape[0], f, n_m))
    else:
        contrib = part
    # base term at the frontier rows (the x axis of a_v is the FULL N)
    a_v = av_op[lab]                                   # (J, N, N_m)
    rows_j = frows[qidx]                               # (J, F)
    a_base = jnp.take_along_axis(a_v, rows_j[:, :, None], axis=1)
    base_rows = jnp.logical_and(start, active)
    contrib = jnp.where(base_rows[:, None, None],
                        jnp.maximum(contrib, a_base), contrib)
    act = jnp.logical_and(active[:, None], rowmask[qidx])
    contrib = jnp.where(act[:, :, None], contrib, zero)
    seg = qidx * k + dst
    scat = jax.ops.segment_max(contrib, seg, num_segments=q_l * k)
    upd = jnp.transpose(scat.reshape(q_l, k, f, n_m), (0, 2, 3, 1))
    new_slab = jnp.maximum(slab, upd)
    changed = jnp.logical_and(
        jnp.any(new_slab > slab, axis=(2, 3)), rowmask)
    if model_axis is not None and model_size > 1:
        changed = jax.lax.pmax(changed.astype(jnp.int32), model_axis) > 0
    return d_op.at[lane, frows].max(new_slab), changed


def _shard_dirty_rows(
    dist_blk: jnp.ndarray,     # (Q_l, N, N_m, K) raw f32 lane block
    src: jnp.ndarray,          # (B,) int32 batch source slots (replicated)
    smask: jnp.ndarray,        # (B,) bool batch padding mask
    query_mask: jnp.ndarray,   # (Q_l,) bool live lanes (replicated)
    model_axis: Optional[str],
    model_size: int,
) -> jnp.ndarray:
    """(Q_l, N) dirty-row mask of a batch on one lane shard: the shard-map
    form of :func:`frontier_seed` / :func:`delete_cone`. The reachability
    reduction runs over the shard's LOCAL u block (the batch sources that
    land in it), partial reach max-combines across the model peers of the
    lane shard (one pmax — the result is then uniform across peers, which
    keeps the skip/run and fallback decisions collective-safe), and the
    global base-term rows x = src fold in from the replicated batch.
    Computed on the RAW timestamp block (conservative superset for
    clock-anchored representations, exact for the float backends)."""
    _q_l, n, n_m, _k = dist_blk.shape
    if model_axis is not None and model_size > 1:
        u_start = jax.lax.axis_index(model_axis) * n_m
    else:
        u_start = 0
    lidx = src - u_start
    lidx = jnp.where(
        jnp.logical_and(smask,
                        jnp.logical_and(lidx >= 0, lidx < n_m)), lidx, n_m)
    src_local = jnp.zeros((n_m,), bool).at[lidx].set(True, mode="drop")
    reach = jnp.any(
        jnp.logical_and(dist_blk > NEG_INF,
                        src_local[None, None, :, None]), axis=(2, 3))
    if model_axis is not None and model_size > 1:
        reach = jax.lax.pmax(reach.astype(jnp.int32), model_axis) > 0
    gidx = jnp.where(smask, src, n)
    src_global = jnp.zeros((n,), bool).at[gidx].set(True, mode="drop")
    return jnp.logical_and(jnp.logical_or(reach, src_global[None, :]),
                           query_mask[:, None])


def shard_frontier_closure(
    dist_blk: jnp.ndarray,
    adj_u: jnp.ndarray,
    adj_v: jnp.ndarray,
    rows: Tuple[jnp.ndarray, ...],
    query_mask: jnp.ndarray,
    src: jnp.ndarray,            # (B,) int32 batch source slots (replicated)
    smask: jnp.ndarray,          # (B,) bool batch padding mask
    f_cap: int,
    backend: BackendLike = "jnp",
    model_axis: Optional[str] = None,
    model_size: int = 1,
    max_rounds: int = 0,
    now: Optional[jnp.ndarray] = None,
    w_max: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, ...]:
    """Shard-local frontier closure: the ingest form of
    :func:`shard_closure` with the frontier gather composed into the
    per-shard skip — a shard SKIPS the closure entirely when its lanes are
    all converged/inert OR the batch dirtied none of its rows (the dirty
    reduction runs over the shard's local u block, max-combined across the
    model peers, so the decision is uniform and collective-free beyond one
    pmax). An overflowing shard falls back to ITS OWN dense loop
    (lax.cond): other shards keep their frontier rounds.

    Returns ``(dist_blk, rounds, query_rounds, rows_relaxed, fell_back,
    seed_rows, max_lane_rows)`` — the last four are this shard's
    :class:`FrontierStats` terms, aggregated host-side by the executor."""
    backend = resolve_backend(backend)
    q_l, n, n_m, k = dist_blk.shape
    bound = max_rounds if max_rounds > 0 else n * k + 1
    dirty = _shard_dirty_rows(dist_blk, src, smask, query_mask,
                              model_axis, model_size)
    frows, rowmask0, cnt = pack_frontier(dirty, f_cap)
    seed_rows = jnp.sum(cnt)
    max_lane_rows = jnp.max(cnt)
    overflow = jnp.any(cnt > f_cap)

    def run(_):
        d_op = backend.encode(dist_blk, now, w_max)
        au_op = backend.encode(adj_u, now, w_max)
        av_op = backend.encode(adj_v, now, w_max)

        def dense(_):
            d_f, it, qr = _shard_dense_loop(
                d_op, au_op, av_op, rows, query_mask, backend,
                model_axis, model_size, bound)
            live_rows = jnp.sum(query_mask.astype(jnp.int32)) * n
            return d_f, it, qr, it * live_rows

        def frontier(_):
            def cond(carry):
                _d, rm, it, _qr, _rr = carry
                return jnp.logical_and(jnp.any(rm), it < bound)

            def body(carry):
                d, rm, it, qr, rr = carry
                nd, changed = _shard_frontier_round(
                    d, au_op, av_op, rows, frows, rm, backend,
                    model_axis, model_size)
                qactive = jnp.any(rm, axis=1).astype(jnp.int32)
                return (nd, changed, it + 1, qr + qactive,
                        rr + jnp.sum(rm.astype(jnp.int32)))

            d_f, _, it, qr, rr = jax.lax.while_loop(
                cond, body,
                (d_op, rowmask0, jnp.asarray(0, jnp.int32),
                 jnp.zeros((q_l,), jnp.int32), jnp.asarray(0, jnp.int32)))
            return d_f, it, qr, rr

        d_f, it, qr, rr = jax.lax.cond(overflow, dense, frontier, None)
        return backend.decode_state(d_f, now, w_max), it, qr, rr

    def skip(_):
        return (dist_blk, jnp.asarray(0, jnp.int32),
                jnp.zeros((q_l,), jnp.int32), jnp.asarray(0, jnp.int32))

    # any dirty row anywhere on this shard? (uniform across model peers:
    # `dirty` folds the pmax'd reach and the replicated masks)
    d, it, qr, rr = jax.lax.cond(jnp.any(cnt > 0), run, skip, None)
    return d, it, qr, rr, overflow, seed_rows, max_lane_rows


def shard_frontier_delete(
    dist_blk: jnp.ndarray,       # (Q_l, N, N_m, K) PRE-delete lane block
    adj_u: jnp.ndarray,          # (L, N_m, N) RETAINED adjacency, u local
    adj_v: jnp.ndarray,          # (L, N, N_m) RETAINED adjacency, v local
    rows: Tuple[jnp.ndarray, ...],
    query_mask: jnp.ndarray,
    src: jnp.ndarray,            # (B,) int32 deleted-edge sources (replicated)
    smask: jnp.ndarray,          # (B,) bool batch padding mask
    f_cap: int,
    backend: BackendLike = "jnp",
    model_axis: Optional[str] = None,
    model_size: int = 1,
    max_rounds: int = 0,
    now: Optional[jnp.ndarray] = None,
    w_max: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, ...]:
    """Shard-local cone-seeded deletion: the delete form of
    :func:`shard_frontier_closure`. The deleted edges' cone is computed on
    the shard's pre-delete block over its LOCAL u rows (pmax-combined
    across model peers — same reduction as ingest, see
    :func:`_shard_dirty_rows`), the cone rows of the local v-column block
    are cleared to the semiring zero, and the shard re-derives them with
    its frontier round loop. A shard none of whose lanes have a cone row
    SKIPS entirely (its rows carry no derivation through the dropped edge,
    so the retained adjacency's fixpoint is already in hand); an
    overflowing shard falls back to ITS OWN dense from-scratch loop (all
    local rows cleared) — the exact non-frontier delete computation, so
    results stay bit-identical per shard.

    Returns the same 7-tuple as :func:`shard_frontier_closure`."""
    backend = resolve_backend(backend)
    q_l, n, n_m, k = dist_blk.shape
    bound = max_rounds if max_rounds > 0 else n * k + 1
    dirty = _shard_dirty_rows(dist_blk, src, smask, query_mask,
                              model_axis, model_size)
    frows, rowmask0, cnt = pack_frontier(dirty, f_cap)
    seed_rows = jnp.sum(cnt)
    max_lane_rows = jnp.max(cnt)
    overflow = jnp.any(cnt > f_cap)
    cleared = jnp.where(dirty[:, :, None, None], NEG_INF, dist_blk)

    def run(_):
        d_op = backend.encode(cleared, now, w_max)
        au_op = backend.encode(adj_u, now, w_max)
        av_op = backend.encode(adj_v, now, w_max)

        def dense(_):
            d0 = backend.encode(jnp.full_like(dist_blk, NEG_INF),
                                now, w_max)
            d_f, it, qr = _shard_dense_loop(
                d0, au_op, av_op, rows, query_mask, backend,
                model_axis, model_size, bound)
            live_rows = jnp.sum(query_mask.astype(jnp.int32)) * n
            return d_f, it, qr, it * live_rows

        def frontier(_):
            def cond(carry):
                _d, rm, it, _qr, _rr = carry
                return jnp.logical_and(jnp.any(rm), it < bound)

            def body(carry):
                d, rm, it, qr, rr = carry
                nd, changed = _shard_frontier_round(
                    d, au_op, av_op, rows, frows, rm, backend,
                    model_axis, model_size)
                qactive = jnp.any(rm, axis=1).astype(jnp.int32)
                return (nd, changed, it + 1, qr + qactive,
                        rr + jnp.sum(rm.astype(jnp.int32)))

            d_f, _, it, qr, rr = jax.lax.while_loop(
                cond, body,
                (d_op, rowmask0, jnp.asarray(0, jnp.int32),
                 jnp.zeros((q_l,), jnp.int32), jnp.asarray(0, jnp.int32)))
            return d_f, it, qr, rr

        d_f, it, qr, rr = jax.lax.cond(overflow, dense, frontier, None)
        return backend.decode_state(d_f, now, w_max), it, qr, rr

    def skip(_):
        return (dist_blk, jnp.asarray(0, jnp.int32),
                jnp.zeros((q_l,), jnp.int32), jnp.asarray(0, jnp.int32))

    d, it, qr, rr = jax.lax.cond(jnp.any(cnt > 0), run, skip, None)
    return d, it, qr, rr, overflow, seed_rows, max_lane_rows
