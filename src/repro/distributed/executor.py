"""MeshExecutor: the batched dense RPQ engine's device work on a mesh.

Layout (reusing the production mesh axis names of launch/mesh.py and the
spec conventions of distributed/sharding.py):

    dist    (Q, N, N, K)  Q -> lane axes (default ``('data',)``), the third
                          (v/u) vertex axis optionally -> 'model'
    emitted (Q, N, N)     Q -> lane axes
    adj     (L, N, N)     v -> 'model' (the closure reshards a u-row view
                          per round; co-locating both views is the ring
                          hillclimb, see launch/dryrun_rpq.py)
    now     ()            replicated

Convergence-aware dispatch — the tentpole win this layer exists for: each
lane shard runs the closure in a shard_map block over ITS OWN transition
rows with the per-query convergence mask device-resident, so

  * a shard whose lanes are all converged/inert SKIPS the round entirely
    (`lax.cond` in semiring.shard_closure) — e.g. seeding a newly
    registered lane relaxes exactly one shard while every other shard does
    zero contraction work;
  * an active shard stops at its OWN fixpoint instead of riding until the
    globally slowest query converges — the ~37% no-op relaxation tail that
    fig12 measured on the single-device path becomes skipped contractions.

The skip is observable in the executor counters: ``shard_rounds_total``
(rounds shards actually relaxed) vs ``n_shards * sync_rounds_total`` (every
shard riding to the global fixpoint); ``skipped_shard_rounds_total`` is
their gap, reported by benchmarks/fig14_sharded_engine.py.

Result streams are BIT-identical to LocalExecutor: the (max, min) semiring
has no floating-point reassociation error, so splitting the u-contraction
into per-shard partials combined by `pmax` is exact, and each query's
fixpoint is independent of every other query (transitions only read their
owning lane's slices).

The per-shard closure contracts with the executor's SELECTED
:class:`~repro.core.backend.ContractionBackend` (PR 4): the fused batched
pallas kernel or the mxu_bucket level mode run per shard exactly as they
do locally (the mesh path used to hardcode the jnp oracle). Identity still
holds per backend — even the bucket mode's quantization is deterministic,
so mesh and local bucket runs emit the same streams.

Tests run this on a host-local CPU mesh
(``XLA_FLAGS=--xla_force_host_platform_device_count=8``, the tier1-sharded
CI job); a single-device mesh degenerates to one shard and still exercises
the shard_map path.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import telemetry
from ..core.executor import (
    BatchedEngineArrays,
    CompactRows,
    DispatchResult,
    Executor,
    QueryTables,
    apply_batch,
    compact_rows,
    drop_batch,
    emit_new,
)
from ..core.sparse_adj import EllAdjacency, ell_to_dense
from ..core.sparse_dist import RowSparseDist, rsd_from_dense, rsd_to_dense
from ..core.semiring import (
    NEG_INF,
    BatchedTransitionTable,
    FrontierStats,
    batched_valid_pairs,
    shard_closure,
    shard_frontier_closure,
    shard_frontier_delete,
    shard_relax_round,
    shard_transitions,
)


def host_mesh(model_axis: int = 1) -> Mesh:
    """('data', 'model') mesh over whatever devices this process has
    (launch/mesh.py's host mesh), clamping the model axis to the device
    count — a 1-device run yields the degenerate 1x1 mesh, so the same
    code path works in every tier."""
    from ..launch.mesh import make_host_mesh

    return make_host_mesh(max(1, min(model_axis, len(jax.devices()))))


def _row_specs(q_axes) -> Tuple[P, ...]:
    return tuple(P(q_axes, None) for _ in range(6))


def _adj_dense(adj):
    """Trace-time layout adapter for the shard_map closures: the per-shard
    relaxation contracts the canonical dense slab (one in-jit densify —
    XLA SPMD inserts the reshard), while the ELL pytree itself carries the
    graph between dispatches so insert/delete scatters stay O(B·E)."""
    return ell_to_dense(adj) if isinstance(adj, EllAdjacency) else adj


def _dist_dense(dist):
    """Trace-time dist layout adapter, the dist twin of :func:`_adj_dense`:
    the shard_map closures relax the canonical dense ``(Q, N, N, K)`` slab
    (one in-jit densify), while the row-sparse pytree carries the reachable
    sets between dispatches so checkpoint/emit state stays compact."""
    return rsd_to_dense(dist) if isinstance(dist, RowSparseDist) else dist


def _dist_like(dist0, dense):
    """Repack a dense closure result into ``dist0``'s layout, carrying its
    capacities and loss counter — identity under the dense layout. The
    repack is a canonical pack (fitting rows -> slots, overfull -> table),
    so the mesh path is observably identical to the local sparse path."""
    if isinstance(dist0, RowSparseDist):
        return rsd_from_dense(dense, dist0.dist_cap, dist0.ovf_cap,
                              dist0.lost)
    return dense


def _dist_logical_shape(dist):
    """Logical dense ``(Q, N, N, K)`` shape of either layout (trace-time
    metadata only — never densifies)."""
    if isinstance(dist, RowSparseDist):
        q, n, _c = dist.idx.shape
        return (q, n, n, dist.k)
    return tuple(dist.shape)


def _adj_shardings(mesh: Mesh, adj_layout: str):
    """Canonical adjacency sharding per layout: the dense slab shards its v
    axis over 'model'; the ELL pytree shards idx/ts on the u-ROW axis (rows
    are the scatter unit) and replicates the small spill ring."""
    if adj_layout == "ell":
        row = NamedSharding(mesh, P(None, "model", None))
        rep = NamedSharding(mesh, P())
        return EllAdjacency(idx=row, ts=row, spill_src=rep, spill_dst=rep,
                            spill_lab=rep, spill_ts=rep, spill_ptr=rep)
    return NamedSharding(mesh, P(None, None, "model"))


def _dist_shardings(mesh: Mesh, dist_layout: str, qa):
    """Canonical dist sharding per layout: the dense slab shards Q over the
    lane axes and v over 'model'; the row-sparse pytree shards its source-row
    slabs on the lane axis only (rows are the gather/scatter unit; the v/k
    entries inside a row are the payload) and replicates the small bounded
    overflow table + counters."""
    if dist_layout == "row_sparse":
        row = NamedSharding(mesh, P(qa, None, None))
        rep = NamedSharding(mesh, P())
        return RowSparseDist(idx=row, ts=row, ovf_rows=rep, ovf_ts=rep,
                             ovf_ptr=rep, lost=rep)
    return NamedSharding(mesh, P(qa, None, "model", None))


def make_sharded_closure(mesh: Mesh, backend,
                         q_axes=("data",), model_axis: str = "model"):
    """shard_map-wrapped per-shard closure: (dist, adj_u, adj_v, rows, mask0,
    now, w_max) -> (dist', shard_rounds (n_shards,), query_rounds (Q,)).
    ``now``/``w_max`` are replicated scalars anchoring clock-dependent
    backend representations (the bucket level grid); each active shard
    encodes its own block (elementwise, collective-free)."""
    qa = q_axes[0] if len(q_axes) == 1 else tuple(q_axes)
    n_model = mesh.shape[model_axis]
    dist_spec = P(qa, None, model_axis, None)

    def body(dist_blk, adj_u, adj_v, *rest):
        rows = tuple(r[0] for r in rest[:6])
        mask0, now, w_max = rest[6], rest[7], rest[8]
        d_f, rounds, qrounds = shard_closure(
            dist_blk, adj_u, adj_v, rows, mask0, backend=backend,
            model_axis=model_axis if n_model > 1 else None,
            model_size=n_model, now=now, w_max=w_max,
        )
        return d_f, rounds.reshape(1), qrounds

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(dist_spec, P(None, model_axis, None), P(None, None, model_axis),
                  *_row_specs(qa), P(qa), P(), P()),
        out_specs=(dist_spec, P(qa), P(qa)),
        check_vma=False,
    )


def make_sharded_frontier_closure(mesh: Mesh, backend, f_cap: int,
                                  q_axes=("data",), model_axis: str = "model"):
    """shard_map-wrapped frontier closure (the ingest form): (dist, adj_u,
    adj_v, rows, mask0, src, smask, now, w_max) -> (dist', shard_rounds,
    query_rounds, rows_relaxed, fell_back, seed_rows, max_lane_rows) with
    the per-shard stats shaped (n_shards,). Each shard seeds its own
    frontier from the (replicated) batch source slots, skips the closure
    entirely when nothing on it is dirty, and falls back to ITS OWN dense
    loop on overflow — other shards keep the frontier rounds."""
    qa = q_axes[0] if len(q_axes) == 1 else tuple(q_axes)
    n_model = mesh.shape[model_axis]
    dist_spec = P(qa, None, model_axis, None)

    def body(dist_blk, adj_u, adj_v, *rest):
        rows = tuple(r[0] for r in rest[:6])
        mask0, src, smask, now, w_max = rest[6:11]
        d_f, rounds, qrounds, rr, fb, seed, mx = shard_frontier_closure(
            dist_blk, adj_u, adj_v, rows, mask0, src, smask, f_cap,
            backend=backend,
            model_axis=model_axis if n_model > 1 else None,
            model_size=n_model, now=now, w_max=w_max,
        )
        return (d_f, rounds.reshape(1), qrounds, rr.reshape(1),
                fb.reshape(1), seed.reshape(1), mx.reshape(1))

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(dist_spec, P(None, model_axis, None), P(None, None, model_axis),
                  *_row_specs(qa), P(qa), P(None), P(None), P(), P()),
        out_specs=(dist_spec, P(qa), P(qa), P(qa), P(qa), P(qa), P(qa)),
        check_vma=False,
    )


def make_sharded_frontier_delete(mesh: Mesh, backend, f_cap: int,
                                 q_axes=("data",), model_axis: str = "model"):
    """shard_map-wrapped cone-seeded deletion: same signature and output
    layout as :func:`make_sharded_frontier_closure`, but each shard
    computes the deleted edges' cone on its PRE-delete block (``adj_u`` /
    ``adj_v`` carry the RETAINED adjacency), clears its cone rows, and
    re-derives them; a shard with no cone rows skips (its lanes carry no
    derivation through the dropped edges), and an overflowing shard falls
    back to ITS OWN dense from-scratch loop."""
    qa = q_axes[0] if len(q_axes) == 1 else tuple(q_axes)
    n_model = mesh.shape[model_axis]
    dist_spec = P(qa, None, model_axis, None)

    def body(dist_blk, adj_u, adj_v, *rest):
        rows = tuple(r[0] for r in rest[:6])
        mask0, src, smask, now, w_max = rest[6:11]
        d_f, rounds, qrounds, rr, fb, seed, mx = shard_frontier_delete(
            dist_blk, adj_u, adj_v, rows, mask0, src, smask, f_cap,
            backend=backend,
            model_axis=model_axis if n_model > 1 else None,
            model_size=n_model, now=now, w_max=w_max,
        )
        return (d_f, rounds.reshape(1), qrounds, rr.reshape(1),
                fb.reshape(1), seed.reshape(1), mx.reshape(1))

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(dist_spec, P(None, model_axis, None), P(None, None, model_axis),
                  *_row_specs(qa), P(qa), P(None), P(None), P(), P()),
        out_specs=(dist_spec, P(qa), P(qa), P(qa), P(qa), P(qa), P(qa)),
        check_vma=False,
    )


def make_sharded_round(mesh: Mesh, backend,
                       q_axes=("data",), model_axis: str = "model"):
    """One convergence-masked relaxation round (no fixpoint loop) with the
    same sharding/skip structure — the unit launch/dryrun_rpq.py lowers for
    the roofline (round count is data-dependent, so cost is per round). The
    backend's representation boundary wraps the single round: an active
    shard encodes, contracts, decodes; a masked shard skips all three."""
    from ..core.backend import resolve_backend

    backend = resolve_backend(backend)
    qa = q_axes[0] if len(q_axes) == 1 else tuple(q_axes)
    n_model = mesh.shape[model_axis]
    dist_spec = P(qa, None, model_axis, None)

    def body(dist_blk, adj_u, adj_v, *rest):
        qidx, src, lab, dst, start, active = (r[0] for r in rest[:6])
        mask0, now, w_max = rest[6], rest[7], rest[8]

        def run(_):
            d_op = backend.encode(dist_blk, now, w_max)
            nd, _changed = shard_relax_round(
                d_op, backend.encode(adj_u, now, w_max),
                backend.encode(adj_v, now, w_max),
                qidx, src, lab, dst, start, active,
                mask0, backend=backend,
                model_axis=model_axis if n_model > 1 else None,
                model_size=n_model)
            return backend.decode_state(nd, now, w_max)

        return jax.lax.cond(jnp.any(mask0), run, lambda _: dist_blk, None)

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(dist_spec, P(None, model_axis, None), P(None, None, model_axis),
                  *_row_specs(qa), P(qa), P(), P()),
        out_specs=dist_spec,
        check_vma=False,
    )


def make_sharded_frontier_round(mesh: Mesh, backend,
                                q_axes=("data",), model_axis: str = "model"):
    """One frontier-restricted relaxation round (no fixpoint loop) with the
    same sharding/skip structure as :func:`make_sharded_round` — the unit
    launch/dryrun_rpq.py lowers so the roofline prices the frontier
    dispatch at O(J·F·N²) instead of the dense O(J·N³). The (Q, F) frontier
    row indices and slot mask ride as runtime, lane-sharded inputs; a shard
    whose rowmask is empty skips encode/contract/decode entirely."""
    from ..core.backend import resolve_backend
    from ..core.semiring import _shard_frontier_round

    backend = resolve_backend(backend)
    qa = q_axes[0] if len(q_axes) == 1 else tuple(q_axes)
    n_model = mesh.shape[model_axis]
    dist_spec = P(qa, None, model_axis, None)

    def body(dist_blk, adj_u, adj_v, *rest):
        rows = tuple(r[0] for r in rest[:6])
        frows, rowmask, now, w_max = rest[6:10]

        def run(_):
            d_op = backend.encode(dist_blk, now, w_max)
            nd, _changed = _shard_frontier_round(
                d_op, backend.encode(adj_u, now, w_max),
                backend.encode(adj_v, now, w_max),
                rows, frows, rowmask, backend,
                model_axis if n_model > 1 else None, n_model)
            return backend.decode_state(nd, now, w_max)

        return jax.lax.cond(jnp.any(rowmask), run, lambda _: dist_blk, None)

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(dist_spec, P(None, model_axis, None), P(None, None, model_axis),
                  *_row_specs(qa), P(qa, None), P(qa, None), P(), P()),
        out_specs=dist_spec,
        check_vma=False,
    )


def frontier_round_lowering(mesh: Mesh, btt: BatchedTransitionTable,
                            q_cap: int, n_slots: int, f_cap: int,
                            q_axes=("data",), backend="jnp"):
    """Dryrun lowering of the frontier round: like
    :func:`batched_round_lowering` but the contraction is restricted to a
    (q_cap, f_cap) frontier — ``round_fn(dist, adj, frows, rowmask, now,
    w_max)``. Returns ``(round_fn, arg_specs, arg_shardings,
    out_sharding)``."""
    n_shards = int(np.prod([mesh.shape[a] for a in q_axes]))
    if q_cap % n_shards:
        raise ValueError(f"q_cap {q_cap} not divisible by {n_shards} lane shards")
    rows = shard_transitions(btt, q_cap, n_shards)
    sharded_round = make_sharded_frontier_round(mesh, backend, q_axes=q_axes)
    qa = q_axes[0] if len(q_axes) == 1 else tuple(q_axes)
    dist_sh = NamedSharding(mesh, P(qa, None, "model", None))
    adj_sh = NamedSharding(mesh, P(None, None, "model"))
    frow_sh = NamedSharding(mesh, P(qa, None))
    scalar_sh = NamedSharding(mesh, P())
    dist_spec = jax.ShapeDtypeStruct((q_cap, n_slots, n_slots, btt.k), jnp.float32)
    adj_spec = jax.ShapeDtypeStruct((btt.n_labels, n_slots, n_slots), jnp.float32)
    frows_spec = jax.ShapeDtypeStruct((q_cap, f_cap), jnp.int32)
    rmask_spec = jax.ShapeDtypeStruct((q_cap, f_cap), jnp.bool_)
    scalar_spec = jax.ShapeDtypeStruct((), jnp.float32)

    def round_fn(dist, adj, frows, rowmask, now, w_max):
        return sharded_round(dist, adj, adj, *rows, frows, rowmask, now, w_max)

    return (round_fn,
            (dist_spec, adj_spec, frows_spec, rmask_spec, scalar_spec,
             scalar_spec),
            (dist_sh, adj_sh, frow_sh, frow_sh, scalar_sh, scalar_sh),
            dist_sh)


def batched_round_lowering(mesh: Mesh, btt: BatchedTransitionTable,
                           q_cap: int, n_slots: int,
                           q_axes=("data",), backend="jnp"):
    """The dryrun lowering of the mesh executor's round: returns
    ``(round_fn, arg_specs, arg_shardings, out_sharding)`` for
    ``round_fn(dist, adj, query_mask, now, w_max)`` with dist
    (q_cap, N, N, K) sharded Q->q_axes / v->'model', the (Q,) convergence
    mask as a runtime, lane-sharded input, and the replicated stream-clock
    scalars a clock-anchored backend (mxu_bucket) quantizes against.
    ``q_cap`` is the lane capacity after padding the live query count up to
    a multiple of the lane-shard count (inert lanes are exactly the
    engine's bucketed padding). ``backend`` selects the contraction
    substrate the cell lowers — the SAME object the engine would run."""
    n_shards = int(np.prod([mesh.shape[a] for a in q_axes]))
    if q_cap % n_shards:
        raise ValueError(f"q_cap {q_cap} not divisible by {n_shards} lane shards")
    rows = shard_transitions(btt, q_cap, n_shards)
    sharded_round = make_sharded_round(mesh, backend, q_axes=q_axes)
    qa = q_axes[0] if len(q_axes) == 1 else tuple(q_axes)
    dist_sh = NamedSharding(mesh, P(qa, None, "model", None))
    adj_sh = NamedSharding(mesh, P(None, None, "model"))
    mask_sh = NamedSharding(mesh, P(qa))
    scalar_sh = NamedSharding(mesh, P())
    dist_spec = jax.ShapeDtypeStruct((q_cap, n_slots, n_slots, btt.k), jnp.float32)
    adj_spec = jax.ShapeDtypeStruct((btt.n_labels, n_slots, n_slots), jnp.float32)
    mask_spec = jax.ShapeDtypeStruct((q_cap,), jnp.bool_)
    scalar_spec = jax.ShapeDtypeStruct((), jnp.float32)

    def round_fn(dist, adj, query_mask, now, w_max):
        return sharded_round(dist, adj, adj, *rows, query_mask, now, w_max)

    return (round_fn,
            (dist_spec, adj_spec, mask_spec, scalar_spec, scalar_spec),
            (dist_sh, adj_sh, mask_sh, scalar_sh, scalar_sh), dist_sh)


def _compact_shardings(mesh: Mesh) -> CompactRows:
    """A dispatch's compacted result rows are replicated: the host reads
    them whole (the full mask keeps the lane sharding of ``emitted``)."""
    rep = NamedSharding(mesh, P())
    return CompactRows(rep, rep, rep)


@functools.lru_cache(maxsize=None)
def _mesh_step_fns(mesh: Mesh, q_axes: Tuple[str, ...], backend,
                   adj_layout: str = "dense", dist_layout: str = "dense"):
    """Jitted mesh step functions + canonical shardings, cached per
    (mesh, lane axes, backend object, adjacency layout, dist layout) so
    every MeshExecutor on the same mesh shares one compile cache (mirroring
    the module-level jits of the local executor; string-named backends
    resolve to process-wide singletons, so the cache key is stable). Under
    ``adj_layout="ell"`` the batch fold / drop runs on the sharded ELL
    pytree and the closures contract a one-shot in-jit densified view —
    bit-identical to the dense layout (see core/sparse_adj.py). Under
    ``dist_layout="row_sparse"`` the closures likewise relax an in-jit
    densified dist and the result repacks into the row-sparse pytree on
    the way out — the shard_map bodies stay layout-oblivious (see
    core/sparse_dist.py)."""
    qa = q_axes[0] if len(q_axes) == 1 else tuple(q_axes)
    sh = dict(
        adj=_adj_shardings(mesh, adj_layout),
        dist=_dist_shardings(mesh, dist_layout, qa),
        emitted=NamedSharding(mesh, P(qa, None, None)),
        now=NamedSharding(mesh, P()),
    )
    closure = make_sharded_closure(mesh, backend, q_axes=q_axes)
    state_sh = BatchedEngineArrays(sh["adj"], sh["dist"], sh["emitted"], sh["now"])
    lane_sh = NamedSharding(mesh, P(qa))
    compact_sh = _compact_shardings(mesh)

    def ingest_impl(arrays, src, dst, lab, ts, mask, ts_floor,
                    rows, finals_mask, windows, live_mask, w_max):
        adj, now = apply_batch(arrays, src, dst, lab, ts, mask, ts_floor)
        adj_d = _adj_dense(adj)
        dist, shard_rounds, qrounds = closure(
            _dist_dense(arrays.dist), adj_d, adj_d, *rows, live_mask, now,
            w_max)
        out, new = emit_new(arrays, dist, adj, now, finals_mask, windows)
        out = out._replace(dist=_dist_like(arrays.dist, dist))
        return out, new, shard_rounds, qrounds, compact_rows(new)

    def delete_impl(arrays, src, dst, lab, mask, ts_now,
                    rows, finals_mask, windows, live_mask, w_max):
        now = jnp.maximum(arrays.now, ts_now)
        low = now - windows
        valid_before = batched_valid_pairs(arrays.dist, finals_mask, low)
        adj = drop_batch(arrays, src, dst, lab, mask)
        adj_d = _adj_dense(adj)
        q, n, _, k = _dist_logical_shape(arrays.dist)
        dist0 = jnp.full((q, n, n, k), NEG_INF, jnp.float32)
        dist, shard_rounds, qrounds = closure(
            dist0, adj_d, adj_d, *rows, live_mask, now, w_max)
        valid_after = batched_valid_pairs(dist, finals_mask, low)
        invalidated = jnp.logical_and(valid_before, jnp.logical_not(valid_after))
        return (BatchedEngineArrays(adj, _dist_like(arrays.dist, dist),
                                    arrays.emitted, now),
                invalidated, shard_rounds, qrounds, compact_rows(invalidated))

    def relax_impl(arrays, rows, query_mask, w_max):
        adj_d = _adj_dense(arrays.adj)
        dist, shard_rounds, qrounds = closure(
            _dist_dense(arrays.dist), adj_d, adj_d, *rows, query_mask,
            arrays.now, w_max)
        return (arrays._replace(dist=_dist_like(arrays.dist, dist)),
                shard_rounds, qrounds)

    return dict(
        shardings=sh,
        ingest=jax.jit(ingest_impl, donate_argnums=(0,),
                       out_shardings=(state_sh, sh["emitted"], lane_sh, lane_sh,
                                      compact_sh)),
        delete=jax.jit(delete_impl, donate_argnums=(0,),
                       out_shardings=(state_sh, sh["emitted"], lane_sh, lane_sh,
                                      compact_sh)),
        relax=jax.jit(relax_impl, donate_argnums=(0,),
                      out_shardings=(state_sh, lane_sh, lane_sh)),
    )


@functools.lru_cache(maxsize=None)
def _mesh_frontier_ingest(mesh: Mesh, q_axes: Tuple[str, ...], backend,
                          f_cap: int, adj_layout: str = "dense",
                          dist_layout: str = "dense"):
    """Jitted frontier ingest for the mesh executor, cached per (mesh, lane
    axes, backend, frontier capacity, layouts) — capacity grows ×2
    like Q/K bucketing, so each step of the auto-growth compiles once and
    the previous steps' entries stay warm for other groups."""
    fns = _mesh_step_fns(mesh, q_axes, backend, adj_layout, dist_layout)
    sh = fns["shardings"]
    qa = q_axes[0] if len(q_axes) == 1 else tuple(q_axes)
    closure = make_sharded_frontier_closure(mesh, backend, f_cap,
                                            q_axes=q_axes)
    state_sh = BatchedEngineArrays(sh["adj"], sh["dist"], sh["emitted"],
                                  sh["now"])
    lane_sh = NamedSharding(mesh, P(qa))

    def ingest_impl(arrays, src, dst, lab, ts, mask, ts_floor,
                    rows, finals_mask, windows, live_mask, w_max):
        adj, now = apply_batch(arrays, src, dst, lab, ts, mask, ts_floor)
        adj_d = _adj_dense(adj)
        dist, shard_rounds, qrounds, rr, fb, seed, mx = closure(
            _dist_dense(arrays.dist), adj_d, adj_d, *rows, live_mask, src,
            mask, now, w_max)
        out, new = emit_new(arrays, dist, adj, now, finals_mask, windows)
        out = out._replace(dist=_dist_like(arrays.dist, dist))
        return (out, new, shard_rounds, qrounds, rr, fb, seed, mx,
                compact_rows(new))

    return jax.jit(
        ingest_impl, donate_argnums=(0,),
        out_shardings=(state_sh, sh["emitted"], lane_sh, lane_sh,
                       lane_sh, lane_sh, lane_sh, lane_sh,
                       _compact_shardings(mesh)))


@functools.lru_cache(maxsize=None)
def _mesh_frontier_delete(mesh: Mesh, q_axes: Tuple[str, ...], backend,
                          f_cap: int, adj_layout: str = "dense",
                          dist_layout: str = "dense"):
    """Jitted cone-seeded deletion for the mesh executor, cached per (mesh,
    lane axes, backend, frontier capacity, layouts) — the delete
    twin of :func:`_mesh_frontier_ingest`, sharing its capacity-bucketing
    discipline."""
    fns = _mesh_step_fns(mesh, q_axes, backend, adj_layout, dist_layout)
    sh = fns["shardings"]
    qa = q_axes[0] if len(q_axes) == 1 else tuple(q_axes)
    closure = make_sharded_frontier_delete(mesh, backend, f_cap,
                                           q_axes=q_axes)
    state_sh = BatchedEngineArrays(sh["adj"], sh["dist"], sh["emitted"],
                                  sh["now"])
    lane_sh = NamedSharding(mesh, P(qa))

    def delete_impl(arrays, src, dst, lab, mask, ts_now,
                    rows, finals_mask, windows, live_mask, w_max):
        now = jnp.maximum(arrays.now, ts_now)
        low = now - windows
        valid_before = batched_valid_pairs(arrays.dist, finals_mask, low)
        adj = drop_batch(arrays, src, dst, lab, mask)
        adj_d = _adj_dense(adj)
        dist, shard_rounds, qrounds, rr, fb, seed, mx = closure(
            _dist_dense(arrays.dist), adj_d, adj_d, *rows, live_mask, src,
            mask, now, w_max)
        valid_after = batched_valid_pairs(dist, finals_mask, low)
        invalidated = jnp.logical_and(valid_before,
                                      jnp.logical_not(valid_after))
        return (BatchedEngineArrays(adj, _dist_like(arrays.dist, dist),
                                    arrays.emitted, now),
                invalidated, shard_rounds, qrounds, rr, fb, seed, mx,
                compact_rows(invalidated))

    return jax.jit(
        delete_impl, donate_argnums=(0,),
        out_shardings=(state_sh, sh["emitted"], lane_sh, lane_sh,
                       lane_sh, lane_sh, lane_sh, lane_sh,
                       _compact_shardings(mesh)))


class MeshExecutor(Executor):
    """Sharded executor: Q lanes over the mesh's data axis (optionally the
    vertex axis over model), convergence-aware per-shard dispatch.

    ``q_multiple`` / ``n_multiple`` advertise the shard counts so the
    engine rounds its lane and vertex capacities to them (inert padding
    lanes land on real shards and are skipped by the mask). State placement
    and every jitted step carry explicit NamedShardings, so checkpoints
    written by a mesh run restore onto a local executor and vice versa
    (arrays are saved logically; placement is re-derived here).
    """

    def __init__(self, mesh: Optional[Mesh] = None, model_axis: int = 1,
                 q_axes: Sequence[str] = ("data",), backend="jnp",
                 frontier: str = "off", frontier_cap: int = 32,
                 adj_layout: str = "dense", ell_cap: int = 8,
                 spill_cap: int = 256, dist_layout: str = "dense",
                 dist_cap: int = 16, dist_ovf_cap: Optional[int] = None):
        super().__init__(backend, frontier=frontier, frontier_cap=frontier_cap,
                         adj_layout=adj_layout, ell_cap=ell_cap,
                         spill_cap=spill_cap, dist_layout=dist_layout,
                         dist_cap=dist_cap, dist_ovf_cap=dist_ovf_cap)
        self.mesh = mesh if mesh is not None else host_mesh(model_axis)
        self.q_axes = tuple(q_axes)
        self.n_shards = int(np.prod([self.mesh.shape[a] for a in self.q_axes]))
        self.n_model = self.mesh.shape["model"]
        self.q_multiple = self.n_shards
        self.n_multiple = self.n_model
        # the RESOLVED backend object keys the cache (stable identity for
        # string-named backends), and its contraction is what the per-shard
        # closure runs — no jnp-oracle hardcode on the mesh path
        fns = _mesh_step_fns(self.mesh, self.q_axes, self.backend,
                             self.adj_layout, self.dist_layout)
        self._sh = fns["shardings"]
        self._jit_ingest = fns["ingest"]
        self._jit_delete = fns["delete"]
        self._jit_relax = fns["relax"]
        # sharded-table cache: rebuilt when the engine's transition table
        # object changes (query lifecycle events), reused across dispatches
        self._rows_src: Optional[BatchedTransitionTable] = None
        self._rows: Optional[Tuple[jnp.ndarray, ...]] = None
        # convergence-aware dispatch accounting (see module docstring)
        self._shard_rounds_total = 0
        self._sync_rounds_total = 0
        self._skipped_shard_rounds_total = 0

    # -- placement -----------------------------------------------------------

    def _put(self, arr: np.ndarray, name: str):
        return jax.device_put(arr, self._sh[name])

    def _put_adj(self, ell):
        # _sh["adj"] is the EllAdjacency-of-shardings tree under
        # adj_layout="ell" (see _adj_shardings): u-rows over 'model',
        # spill ring replicated
        return jax.device_put(ell, self._sh["adj"])

    def _put_dist(self, sd):
        # _sh["dist"] is the RowSparseDist-of-shardings tree under
        # dist_layout="row_sparse" (see _dist_shardings): source rows over
        # the lane axes, overflow table + counters replicated
        return jax.device_put(sd, self._sh["dist"])

    def _rows_for(self, btt: BatchedTransitionTable, q_cap: int):
        if self._rows_src is not btt:
            self._rows = shard_transitions(btt, q_cap, self.n_shards)
            self._rows_src = btt
        return self._rows

    # -- Executor interface --------------------------------------------------

    def ingest_batch(self, src, dst, lab, ts, mask, ts_floor: float,
                     tables: QueryTables):
        with telemetry.span("executor.dispatch", len(src)):
            q_cap = self.dist_shape[0]
            rows = self._rows_for(tables.btt, q_cap)
            with telemetry.span("executor.reserve"):
                if self.adj_layout == "ell":
                    self._reserve_spill(len(src))
                if self.dist_layout == "row_sparse":
                    self._reserve_dist(self.frontier != "off")
            if self.frontier != "off":
                ingest = _mesh_frontier_ingest(
                    self.mesh, self.q_axes, self.backend, self.frontier_cap,
                    self.adj_layout, self.dist_layout)
                (self._arrays, new, shard_rounds, qrounds,
                 rr, fb, seed, mx, compact) = ingest(
                    self._arrays,
                    jnp.asarray(src), jnp.asarray(dst), jnp.asarray(lab),
                    jnp.asarray(ts), jnp.asarray(mask),
                    jnp.asarray(ts_floor, jnp.float32),
                    rows, tables.finals_mask, tables.windows, tables.live_mask,
                    jnp.asarray(tables.max_window, jnp.float32),
                )
                self._account(shard_rounds, qrounds, tables.n_live,
                              FrontierStats(seed, mx, rr, fb))
                self.steps += 1
                return DispatchResult(compact, new)
            (self._arrays, new, shard_rounds, qrounds,
             compact) = self._jit_ingest(
                self._arrays,
                jnp.asarray(src), jnp.asarray(dst), jnp.asarray(lab),
                jnp.asarray(ts), jnp.asarray(mask),
                jnp.asarray(ts_floor, jnp.float32),
                rows, tables.finals_mask, tables.windows, tables.live_mask,
                jnp.asarray(tables.max_window, jnp.float32),
            )
            self._account(shard_rounds, qrounds, tables.n_live)
            self.steps += 1
            return DispatchResult(compact, new)

    def delete_batch(self, src, dst, lab, mask, ts_now: float,
                     tables: QueryTables):
        with telemetry.span("executor.dispatch", len(src)):
            q_cap = self.dist_shape[0]
            rows = self._rows_for(tables.btt, q_cap)
            with telemetry.span("executor.reserve"):
                if self.dist_layout == "row_sparse":
                    self._reserve_dist(self.frontier != "off")
            if self.frontier != "off":
                delete = _mesh_frontier_delete(
                    self.mesh, self.q_axes, self.backend, self.frontier_cap,
                    self.adj_layout, self.dist_layout)
                (self._arrays, invalidated, shard_rounds, qrounds,
                 rr, fb, seed, mx, compact) = delete(
                    self._arrays,
                    jnp.asarray(src), jnp.asarray(dst), jnp.asarray(lab),
                    jnp.asarray(mask), jnp.asarray(ts_now, jnp.float32),
                    rows, tables.finals_mask, tables.windows, tables.live_mask,
                    jnp.asarray(tables.max_window, jnp.float32),
                )
                self._account(shard_rounds, qrounds, tables.n_live,
                              FrontierStats(seed, mx, rr, fb), is_delete=True)
                self.steps += 1
                return DispatchResult(compact, invalidated)
            (self._arrays, invalidated, shard_rounds, qrounds,
             compact) = self._jit_delete(
                self._arrays,
                jnp.asarray(src), jnp.asarray(dst), jnp.asarray(lab),
                jnp.asarray(mask), jnp.asarray(ts_now, jnp.float32),
                rows, tables.finals_mask, tables.windows, tables.live_mask,
                jnp.asarray(tables.max_window, jnp.float32),
            )
            self._account(shard_rounds, qrounds, tables.n_live)
            self.steps += 1
            return DispatchResult(compact, invalidated)

    def relax(self, tables: QueryTables,
              query_mask: Optional[np.ndarray] = None) -> None:
        q_cap = self.dist_shape[0]
        rows = self._rows_for(tables.btt, q_cap)
        if self.dist_layout == "row_sparse":
            self._reserve_dist(False)
        mask = tables.live_mask if query_mask is None else jnp.asarray(
            np.asarray(query_mask, bool))
        self._arrays, shard_rounds, qrounds = self._jit_relax(
            self._arrays, rows, mask,
            jnp.asarray(tables.max_window, jnp.float32))
        self._account(shard_rounds, qrounds, tables.n_live)

    # -- accounting ----------------------------------------------------------

    def _consume_count(self, shard_rounds, qrounds, n_live: int) -> None:
        sr = np.asarray(shard_rounds)
        sync = int(sr.max()) if sr.size else 0
        self._rounds_total += sync
        self._sync_rounds_total += sync
        self._shard_rounds_total += int(sr.sum())
        self._skipped_shard_rounds_total += int((sync - sr).sum())
        self._query_rounds_total += int(np.asarray(qrounds).sum())
        self._unmasked_query_rounds_total += n_live * sync

    @property
    def shard_rounds_total(self) -> int:
        """Rounds shards ACTUALLY relaxed (skip-aware), summed over shards
        and dispatches."""
        self._flush_counts()
        return self._shard_rounds_total

    @property
    def sync_rounds_total(self) -> int:
        """Per-dispatch max over shards, summed — the rounds every shard
        would ride in a convergence-oblivious (bulk-synchronous) regime."""
        self._flush_counts()
        return self._sync_rounds_total

    @property
    def skipped_shard_rounds_total(self) -> int:
        """Shard-rounds of contraction work the convergence-aware dispatch
        skipped: ``n_shards * sync_rounds_total - shard_rounds_total``."""
        self._flush_counts()
        return self._skipped_shard_rounds_total
