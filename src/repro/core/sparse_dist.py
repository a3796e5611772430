"""Row-sparse dist: per-source-row reachable sets + bounded overflow.

The dense engine stores closure state as a ``(Q, N, N, K)`` timestamp
slab — at N=100k a single K=2 query needs ~80 GB, so dist memory and
the O(Q·N²) emit scan cap N even though the PR 8 adjacency is already
∝ live edges.  But each ``(q, x)`` source row is an independent
single-source problem (the (max, min) recurrence couples
``dist[q, x, v, t]`` only to ``dist[q, x, u, s]`` — the same row), and
on sparse streaming windows almost every ``(v, k)`` entry of a row is
unreachable (``-inf``).  This module is the sparse alternative: per
``(q, x)`` row we keep at most ``dist_cap`` reachable entries
(``idx``/``ts`` slot pairs, ``idx`` a flattened ``v * K + k`` key),
where ``dist_cap`` is a power-of-2 capacity bucketed exactly like the
Q/F/ELL capacities so jit compile caches are reused.

Rows can overflow.  Overflow never loses an entry and never aborts the
dispatch: a row that exceeds ``dist_cap`` is routed to the *overflow
table* — ``ovf_rows`` row ids plus full dense ``ovf_ts`` rows — inside
the same jitted step (``rsd_scatter_rows``), the exact row-granular
form of the frontier's ``lax.cond`` dense-superset fallback.  The host
keeps a conservative budget of how many rows could have claimed
overflow slots since the last drain and re-packs (growing ``dist_cap``
×2) before the table can fill, so the row-sparse layout is
bit-identical to the dense slab at every observable point — the
contract docs/invariants.md records as the row-sparse overflow
contract.

A row lives EITHER in its slots OR in the overflow table (slots are
cleared when a row is routed to overflow), so every read path may
max-fold both regions without double counting.  Free slots hold
``ts == NEG_INF``; their ``idx`` may be stale, which is benign
everywhere the ELL layout's stale indices are (max folds, threshold
reads).

Everything here except ``pack_rows`` (host-side, numpy) is traceable
and runs inside the executor's jitted step functions.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = float("-inf")


class RowSparseDist(NamedTuple):
    """Row-sparse closure state (a pytree; jit-transparent).

    ``ts``/``ovf_ts`` dtype is float32 in executor state — the
    canonical inter-dispatch representation.  Backend encodes happen on
    the dense slabs gathered FROM this structure (the frontier slab,
    the fallback densify), never on the structure itself, so clock-
    anchored backends see exactly the operands the dense layout feeds
    them.
    """

    idx: jax.Array       # (Q, N, C) int32 — flattened v * K + k key per slot
    ts: jax.Array        # (Q, N, C)       — entry timestamp; NEG_INF = free
    ovf_rows: jax.Array  # (R,) int32 — flattened q * N + x row id; -1 = free
    ovf_ts: jax.Array    # (R, N*K)   — full dense overflow rows
    ovf_ptr: jax.Array   # () int32 — claim cursor; host budget keeps < R
    lost: jax.Array      # () int32 — rows dropped with the table full

    @property
    def n_lanes(self) -> int:
        return self.idx.shape[0]

    @property
    def n_slots(self) -> int:
        return self.idx.shape[1]

    @property
    def dist_cap(self) -> int:
        return self.idx.shape[2]

    @property
    def ovf_cap(self) -> int:
        return self.ovf_rows.shape[0]

    @property
    def k(self) -> int:
        return self.ovf_ts.shape[1] // self.idx.shape[1]


def rsd_empty_np(q: int, n: int, k: int, dist_cap: int,
                 ovf_cap: int) -> RowSparseDist:
    """Host-side empty row-sparse state (mirrors ``Executor.init_state``)."""
    return RowSparseDist(
        idx=np.zeros((q, n, dist_cap), np.int32),
        ts=np.full((q, n, dist_cap), NEG_INF, np.float32),
        ovf_rows=np.full((ovf_cap,), -1, np.int32),
        ovf_ts=np.full((ovf_cap, n * k), NEG_INF, np.float32),
        ovf_ptr=np.zeros((), np.int32),
        lost=np.zeros((), np.int32),
    )


def pack_rows(dense: np.ndarray, dist_cap: int,
              ovf_cap: int) -> RowSparseDist:
    """Host-side pack of a dense ``(Q, N, N, K)`` slab into row sets.

    Rows whose finite-entry count fits ``dist_cap`` go to slots; the
    rest go to the overflow table.  The caller sizes ``ovf_cap`` to at
    least the overflowing-row count (``Executor.place`` grows it ×2
    until it fits); raising instead of silently dropping keeps the
    repack→drain invariant auditable.
    """
    dense = np.asarray(dense, np.float32)
    q, n, _, k = dense.shape
    out = rsd_empty_np(q, n, k, dist_cap, ovf_cap)
    flat = dense.reshape(q, n, n * k)
    finite = flat > NEG_INF
    counts = finite.sum(-1)
    over_q, over_x = np.nonzero(counts > dist_cap)
    if over_q.size > ovf_cap:
        raise ValueError(
            f"pack_rows: {over_q.size} rows exceed dist_cap={dist_cap} but "
            f"ovf_cap={ovf_cap}; grow the capacity before packing")
    fit_q, fit_x, fit_e = np.nonzero(
        finite & (counts <= dist_cap)[:, :, None])
    if fit_q.size:
        rank = (np.cumsum(finite, axis=-1) - 1)[fit_q, fit_x, fit_e]
        out.idx[fit_q, fit_x, rank] = fit_e
        out.ts[fit_q, fit_x, rank] = flat[fit_q, fit_x, fit_e]
    if over_q.size:
        slots = np.arange(over_q.size)
        out.ovf_rows[slots] = over_q.astype(np.int64) * n + over_x
        out.ovf_ts[slots] = flat[over_q, over_x]
        out.ovf_ptr[...] = over_q.size
    return out


def rsd_to_dense(sd: RowSparseDist) -> jax.Array:
    """Densify to the canonical ``(Q, N, N, K)`` slab (traceable).

    Exact inverse of ``pack_rows`` up to slot order: max-folding makes
    free slots and the slots-XOR-overflow row split no-ops.
    """
    q, n, _c = sd.idx.shape
    e = sd.ovf_ts.shape[1]
    k = e // n
    flat = jnp.full((q, n, e), NEG_INF, sd.ts.dtype)
    flat = flat.at[jnp.arange(q)[:, None, None],
                   jnp.arange(n)[None, :, None], sd.idx].max(sd.ts)
    live = sd.ovf_rows >= 0
    row = jnp.where(live, sd.ovf_rows, 0)
    vals = jnp.where(live[:, None], sd.ovf_ts, NEG_INF)
    flat = flat.at[row // n, row % n].max(vals)
    return flat.reshape(q, n, n, k)


def rsd_from_dense(dense: jax.Array, dist_cap: int, ovf_cap: int,
                   lost: Optional[jax.Array] = None) -> RowSparseDist:
    """Full in-jit repack of a dense ``(Q, N, N, K)`` slab.

    The traced twin of ``pack_rows`` — the tail of every dense-superset
    path (the frontier fallback branch, the non-frontier round trip):
    fitting rows pack their finite entries into slots by cumsum rank,
    overflowing rows claim fresh overflow slots in row order, and rows
    beyond ``ovf_cap`` are counted into ``lost`` (the host budget keeps
    this leg unreachable; a nonzero count is a detectable, repairable
    condition — see docs/invariants.md).
    """
    q, n, _, k = dense.shape
    e = n * k
    flat = dense.reshape(q, n, e)
    finite = flat > NEG_INF
    counts = jnp.sum(finite, axis=-1)
    fits = counts <= dist_cap
    rank = jnp.cumsum(finite, axis=-1) - 1
    pos = jnp.where(finite & fits[:, :, None], rank, dist_cap)
    lane = jnp.arange(q)[:, None, None]
    slot = jnp.arange(n)[None, :, None]
    cols = jnp.broadcast_to(jnp.arange(e, dtype=jnp.int32), (q, n, e))
    idx = jnp.zeros((q, n, dist_cap), jnp.int32).at[
        lane, slot, pos].set(cols, mode="drop")
    ts = jnp.full((q, n, dist_cap), NEG_INF, flat.dtype).at[
        lane, slot, pos].set(flat, mode="drop")
    over = (~fits).reshape(q * n)
    opos = jnp.where(over, jnp.cumsum(over) - 1, ovf_cap)
    ovf_rows = jnp.full((ovf_cap,), -1, jnp.int32).at[opos].set(
        jnp.arange(q * n, dtype=jnp.int32), mode="drop")
    ovf_ts = jnp.full((ovf_cap, e), NEG_INF, flat.dtype).at[opos].set(
        flat.reshape(q * n, e), mode="drop")
    n_over = jnp.sum(over).astype(jnp.int32)
    dropped = jnp.maximum(n_over - ovf_cap, 0)
    base = jnp.asarray(0, jnp.int32) if lost is None else lost
    return RowSparseDist(idx, ts, ovf_rows, ovf_ts,
                         jnp.minimum(n_over, ovf_cap), base + dropped)


def rsd_empty_like(sd: RowSparseDist) -> RowSparseDist:
    """Every row cleared — the from-scratch ``dist0`` of the dense delete
    path. ``lost`` is preserved (a monotone diagnostic, never reset);
    ``idx`` is left stale, which free slots make benign."""
    return sd._replace(ts=jnp.full_like(sd.ts, NEG_INF),
                       ovf_rows=jnp.full_like(sd.ovf_rows, -1),
                       ovf_ts=jnp.full_like(sd.ovf_ts, NEG_INF),
                       ovf_ptr=jnp.zeros_like(sd.ovf_ptr))


def _ovf_lookup(sd: RowSparseDist, key: jax.Array):
    """Overflow-table membership for flattened row keys (any shape):
    returns ``(has, slot)`` — free entries (-1) never match (keys are
    >= 0)."""
    match = key[..., None] == sd.ovf_rows
    return jnp.any(match, axis=-1), jnp.argmax(match, axis=-1)


def rsd_gather_rows(sd: RowSparseDist, rows: jax.Array,
                    gather_fn=None) -> jax.Array:
    """Densify the frontier rows: ``out[q, f] == dense[q, rows[q, f]]``
    of shape (Q, F, N, K) — the slab the frontier round loop relaxes.

    ``gather_fn(idx, ts, e) -> (M, E)`` is the backend's slot-densify
    hook (``ContractionBackend.gather_dist_rows``); overflow rows fold
    in afterwards with plain jnp (at most one table hit per row).
    Operands and results are raw f32 timestamps — the caller encodes
    the slab at the backend boundary, exactly where the dense layout
    encodes its gathered slab.
    """
    if gather_fn is None:
        from ..kernels.rowsparse.ops import rowsparse_gather
        gather_fn = rowsparse_gather
    q, n, c = sd.idx.shape
    e = sd.ovf_ts.shape[1]
    k = e // n
    f = rows.shape[1]
    lane = jnp.arange(q)[:, None]
    sid = sd.idx[lane, rows]                       # (Q, F, C)
    sts = sd.ts[lane, rows]
    flat = gather_fn(sid.reshape(q * f, c),
                     sts.reshape(q * f, c), e).reshape(q, f, e)
    has, oslot = _ovf_lookup(sd, lane * n + rows)  # (Q, F)
    flat = jnp.where(has[:, :, None],
                     jnp.maximum(flat, sd.ovf_ts[oslot]), flat)
    return flat.reshape(q, f, n, k)


def rsd_scatter_rows(sd: RowSparseDist, rows: jax.Array,
                     rowmask: jax.Array, slab: jax.Array) -> RowSparseDist:
    """Scatter relaxed frontier rows back into the row sets — the
    in-dispatch half of the overflow contract.

    Each valid ``(q, f)`` slot holds the COMPLETE new value of row
    ``rows[q, f]`` (the slab starts as the gathered row and only grows
    under the max fold for inserts; deletes re-derive from scratch), so
    the write is a full-row overwrite — exact even when a row shrinks:

    * rows already in the overflow table overwrite their table row;
    * rows whose finite count fits ``dist_cap`` overwrite all C slots
      with their finite entries in column order, free past the count
      (so stale high-rank entries die);
    * rows newly exceeding ``dist_cap`` claim fresh table slots at the
      cursor (their slots are cleared — a row lives in one region);
    * claims past ``ovf_cap`` drop the row and count into ``lost`` —
      unreachable under the host budget (``Executor._reserve_dist``).

    Every write is row-granular: at most Q·F index tuples per scatter.
    Valid frontier rows are unique per lane (``pack_frontier`` packs a
    mask), so the scatters are collision-free; masked padding slots are
    routed to drop sentinels. Free slots written here get ``idx`` 0.
    """
    q, f, n, k = slab.shape
    e = n * k
    c = sd.idx.shape[2]
    r = sd.ovf_rows.shape[0]
    flat = slab.reshape(q, f, e)
    finite = flat > NEG_INF
    counts = jnp.sum(finite, axis=-1)                    # (Q, F)
    fits = counts <= c
    lane = jnp.arange(q)[:, None]
    key = lane * n + rows
    in_ovf, oslot = _ovf_lookup(sd, key)
    # -- overflow-table writes (existing hit, or fresh claim in order)
    new_claim = rowmask & ~fits & ~in_ovf
    crank = (jnp.cumsum(new_claim.reshape(-1)) - 1).reshape(q, f)
    dest = jnp.where(in_ovf, oslot, sd.ovf_ptr + crank)
    write_ovf = rowmask & (in_ovf | ~fits)
    dest = jnp.where(write_ovf, jnp.minimum(dest, r), r)  # r = drop sentinel
    ovf_rows2 = sd.ovf_rows.at[dest].set(key, mode="drop")
    ovf_ts2 = sd.ovf_ts.at[dest].set(flat, mode="drop")
    n_new = jnp.sum(new_claim).astype(jnp.int32)
    dropped = jnp.sum(new_claim & (sd.ovf_ptr + crank >= r)).astype(jnp.int32)
    # -- slot writes: one full C-wide row per valid slot. Slot j of a
    # fitting row holds its j-th finite column, which is the number of
    # columns whose inclusive finite count is <= j (a fused
    # compare-reduce; nothing (Q, F, C, E)-sized is materialised).
    # Rows routed to the table get an all-free row: the clear.
    incl = jnp.cumsum(finite, axis=-1, dtype=jnp.int32)   # (Q, F, E)
    slot = jnp.arange(c, dtype=jnp.int32)
    col = jnp.sum(incl[:, :, None, :] <= slot[:, None], axis=-1,
                  dtype=jnp.int32)                        # (Q, F, C)
    write_slots = rowmask & fits & ~in_ovf
    used = write_slots[:, :, None] & (slot < counts[:, :, None])
    col = jnp.where(used, col, 0)
    row_ts = jnp.where(used, jnp.take_along_axis(flat, col, axis=-1),
                       NEG_INF)
    wrow = jnp.where(rowmask, rows, n)                    # n = drop sentinel
    idx2 = sd.idx.at[lane, wrow].set(col, mode="drop")
    ts2 = sd.ts.at[lane, wrow].set(row_ts, mode="drop")
    return RowSparseDist(idx2, ts2, ovf_rows2, ovf_ts2,
                         jnp.minimum(sd.ovf_ptr + n_new, r),
                         sd.lost + dropped)


def rsd_seed_gathered(sd: RowSparseDist, src: jax.Array, smask: jax.Array,
                      query_mask: Optional[jax.Array] = None) -> jax.Array:
    """(Q, N) dirty-row mask of a batch — the row-sparse twin of
    :func:`~repro.core.semiring.frontier_seed`, walking only stored
    entries: O(Q·N·C + R·N·K) instead of the dense O(Q·N²·K) scan.
    Exact: the slots and overflow rows hold exactly the finite entries
    the dense reduction tests, and free slots cannot hit."""
    q, n, _c = sd.idx.shape
    e = sd.ovf_ts.shape[1]
    k = e // n
    with jax.named_scope("frontier_seed"):
        idx_b = jnp.where(smask, src, n)
        src_mask = jnp.zeros((n,), bool).at[idx_b].set(True, mode="drop")
        hit = (sd.ts > NEG_INF) & src_mask[sd.idx // k]
        reach = jnp.any(hit, axis=-1).astype(jnp.int32)        # (Q, N)
        live = sd.ovf_rows >= 0
        row = jnp.where(live, sd.ovf_rows, 0)
        ovf = sd.ovf_ts.reshape(-1, n, k)
        hit_r = jnp.any((ovf > NEG_INF) & src_mask[None, :, None],
                        axis=(1, 2)) & live
        reach = reach.at[row // n, row % n].max(hit_r.astype(jnp.int32))
        dirty = (reach > 0) | src_mask[None, :]
        if query_mask is not None:
            dirty = dirty & query_mask[:, None]
    return dirty


def rsd_valid_pairs(sd: RowSparseDist, finals: jax.Array,
                    low: jax.Array) -> jax.Array:
    """(Q, N, N) bool validity per query — the sparse emit.

    The dense scan reduces all Q·N²·K entries against the finals mask
    and the window threshold; here only stored entries contribute:
    slot entries scatter-or into their (q, x, v) cell, overflow rows
    reduce their dense row once.  Identical to
    ``batched_valid_pairs(rsd_to_dense(sd), finals, low)`` — a free
    slot's -inf can never clear a finite threshold.
    """
    q, n, _c = sd.idx.shape
    e = sd.ovf_ts.shape[1]
    k = e // n
    lane = jnp.arange(q)[:, None, None]
    slot = jnp.arange(n)[None, :, None]
    ok = (finals[lane, sd.idx % k] & (sd.ts > low[:, None, None]))
    valid = jnp.zeros((q, n, n), jnp.int32).at[
        lane, slot, sd.idx // k].max(ok.astype(jnp.int32))
    live = sd.ovf_rows >= 0
    row = jnp.where(live, sd.ovf_rows, 0)
    q_r = row // n
    ovf = sd.ovf_ts.reshape(-1, n, k)
    ok_r = jnp.any((ovf > low[q_r][:, None, None])
                   & finals[q_r][:, None, :], axis=2)
    ok_r = ok_r & live[:, None]
    valid = valid.at[q_r, row % n].max(ok_r.astype(jnp.int32))
    return valid > 0


def rsd_clear_slots(sd: RowSparseDist, dead: jax.Array) -> RowSparseDist:
    """Clear every entry whose source OR destination vertex slot is
    dead (``dead``: (N,) bool), mirroring the dense row+column
    ``.set(NEG_INF)`` of ``Executor._clear_slots``."""
    _q, n, _c = sd.idx.shape
    e = sd.ovf_ts.shape[1]
    k = e // n
    ts = jnp.where(dead[None, :, None], NEG_INF, sd.ts)       # source rows
    ts = jnp.where(dead[sd.idx // k], NEG_INF, ts)            # dest entries
    live = sd.ovf_rows >= 0
    row = jnp.where(live, sd.ovf_rows, 0)
    kill_row = dead[row % n] & live                           # (R,)
    ovf = sd.ovf_ts.reshape(-1, n, k)
    ovf = jnp.where(dead[None, :, None], NEG_INF, ovf)        # dest slots
    ovf = jnp.where(kill_row[:, None, None], NEG_INF, ovf)
    return sd._replace(ts=ts, ovf_ts=ovf.reshape(sd.ovf_ts.shape))


def rsd_clear_lane(sd: RowSparseDist, lane: jax.Array) -> RowSparseDist:
    """Clear one query lane (mirrors the dense ``dist.at[lane].set``)."""
    n = sd.idx.shape[1]
    live = sd.ovf_rows >= 0
    hit = (jnp.where(live, sd.ovf_rows, -1) // n) == lane
    return sd._replace(
        ts=sd.ts.at[lane].set(NEG_INF),
        ovf_ts=jnp.where(hit[:, None], NEG_INF, sd.ovf_ts))


def rsd_row_counts(sd: RowSparseDist) -> jax.Array:
    """(Q, N) finite-entry count per row (slots + overflow) — the
    occupancy signal drains size ``dist_cap`` growth from."""
    n = sd.idx.shape[1]
    counts = jnp.sum(sd.ts > NEG_INF, axis=-1).astype(jnp.int32)
    live = sd.ovf_rows >= 0
    row = jnp.where(live, sd.ovf_rows, 0)
    ovf_counts = jnp.where(
        live, jnp.sum(sd.ovf_ts > NEG_INF, axis=-1), 0).astype(jnp.int32)
    return counts.at[row // n, row % n].add(ovf_counts)


def rsd_live_entries(sd: RowSparseDist) -> jax.Array:
    """Device count of finite entries — occupancy telemetry (read only
    at drain boundaries, like ``ell_live_edges``)."""
    live = sd.ovf_rows >= 0
    return (jnp.sum(sd.ts > NEG_INF).astype(jnp.int32)
            + jnp.sum((sd.ovf_ts > NEG_INF)
                      & live[:, None]).astype(jnp.int32))


def rsd_grow_repack(sd: RowSparseDist, dist_cap: int,
                    ovf_cap: int) -> RowSparseDist:
    """Re-pack into grown capacities WITHOUT densifying (O(Q·N·C + R·E)
    instead of O(Q·N²·K)) — the drain-boundary representation change.

    Slot rows copy over (capacity only grows); live overflow rows whose
    finite count now fits ``dist_cap`` pack into their slots, the rest
    re-claim compacted overflow positions.  Pure representation change:
    densify before == densify after (the drain invariant).
    """
    q, n, c = sd.idx.shape
    e = sd.ovf_ts.shape[1]
    pad_c = dist_cap - c
    idx = jnp.pad(sd.idx, ((0, 0), (0, 0), (0, pad_c)))
    ts = jnp.pad(sd.ts, ((0, 0), (0, 0), (0, pad_c)),
                 constant_values=NEG_INF)
    live = sd.ovf_rows >= 0
    finite = (sd.ovf_ts > NEG_INF) & live[:, None]            # (R, E)
    counts = jnp.sum(finite, axis=-1)
    fits = live & (counts <= dist_cap)
    row = jnp.where(live, sd.ovf_rows, 0)
    q_r, x_r = row // n, row % n
    # pack fitting overflow rows into their (now larger) slot rows
    rank = jnp.cumsum(finite, axis=-1) - 1
    pos = jnp.where(finite & fits[:, None], rank, dist_cap)
    cols = jnp.broadcast_to(jnp.arange(e, dtype=jnp.int32),
                            sd.ovf_ts.shape)
    idx = idx.at[q_r[:, None], x_r[:, None], pos].set(cols, mode="drop")
    ts = ts.at[q_r[:, None], x_r[:, None], pos].set(sd.ovf_ts, mode="drop")
    # compact the remaining overflow rows into the (possibly grown) table
    overs = live & ~fits
    opos = jnp.where(overs, jnp.cumsum(overs) - 1, ovf_cap)
    ovf_rows = jnp.full((ovf_cap,), -1, jnp.int32).at[opos].set(
        sd.ovf_rows, mode="drop")
    ovf_ts = jnp.full((ovf_cap, e), NEG_INF, sd.ovf_ts.dtype).at[
        opos].set(sd.ovf_ts, mode="drop")
    return RowSparseDist(idx, ts, ovf_rows, ovf_ts,
                         jnp.sum(overs).astype(jnp.int32), sd.lost)
