"""Row-sparse dist conformance (PR 9 tentpole).

The row-sparse layout must be BIT-identical to the dense (Q, N, N, K)
slab — per event, on both executors, under all three contraction
backends, with the frontier on and off, through deletions, expiry,
per-row overflow (bounded table + ×2 ``dist_cap`` growth), vertex-axis
growth/compaction, query churn, and checkpoints in both directions. The
dense layout is the oracle: every reachable (v, k) entry is folded with
the same (max, min) semantics wherever it lives (row slot or overflow
table), and free slots / stale duplicates annihilate under the max fold
(see core/sparse_dist.py).

Under the mxu_bucket backend identity is OBSERVABLE rather than bitwise:
window-dead entries a sparse row never re-encodes sit below every read
threshold, so emitted streams and valid-pair sets match exactly while
raw timestamps may differ in GC'd cells (the PR 6 deletion precedent).

The mesh legs run on whatever devices this process has (the CI
sparse-dist leg re-runs this file under
XLA_FLAGS=--xla_force_host_platform_device_count=8 so the lane-sharded
row slabs compose with the in-jit densify).
"""
import random
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import compile_query
from repro.core.backend import BucketBackend, PallasBackend
from repro.core.engine import BatchedDenseRPQEngine, RegisteredQuery
from repro.core.executor import LocalExecutor
from repro.core.semiring import NEG_INF, batched_valid_pairs, frontier_seed
from repro.core.sparse_dist import (
    RowSparseDist,
    pack_rows,
    rsd_from_dense,
    rsd_gather_rows,
    rsd_grow_repack,
    rsd_live_entries,
    rsd_row_counts,
    rsd_scatter_rows,
    rsd_seed_gathered,
    rsd_to_dense,
    rsd_valid_pairs,
)
from repro.distributed.executor import MeshExecutor
from repro.kernels.rowsparse import (
    rowsparse_gather,
    rowsparse_gather_naive,
    rowsparse_gather_ref,
)
from repro.streaming.service import PersistentQueryService

QUERIES = ["a*", "a . b*", "(a | b)*", "a . b* . c", "(a . b)+", "a . b . c"]
LABELS = ["a", "b", "c"]


# -- unit: pack / densify / mutate ------------------------------------------


def _dev(sd):
    """pack_rows builds on host numpy; device-place before traced ops
    (the executor's _put_dist does the same)."""
    return jax.tree_util.tree_map(jnp.asarray, sd)


def _random_dense_dist(rng, q=2, n=10, k=3, density=0.2):
    d = np.full((q, n, n, k), NEG_INF, np.float32)
    for _ in range(int(q * n * n * k * density)):
        d[rng.randrange(q), rng.randrange(n), rng.randrange(n),
          rng.randrange(k)] = float(rng.randrange(1, 50))
    return d


@pytest.mark.parametrize("seed", range(4))
def test_pack_densify_round_trip(seed):
    rng = random.Random(seed)
    dense = _random_dense_dist(rng)
    cap = int(max((dense > NEG_INF).reshape(2, 10, -1).sum(-1).max(), 1))
    sd = pack_rows(dense, cap, 64)
    np.testing.assert_array_equal(np.asarray(rsd_to_dense(sd)), dense)
    assert int(rsd_live_entries(sd)) == int((dense > NEG_INF).sum())
    # tiny cap: overfull rows route to the table, densify still exact
    sd2 = pack_rows(dense, 1, 64)
    np.testing.assert_array_equal(np.asarray(rsd_to_dense(sd2)), dense)
    assert int(sd2.ovf_ptr) > 0


def test_pack_rejects_overfull_table():
    dense = np.full((1, 4, 4, 2), 5.0, np.float32)  # every row holds 8
    with pytest.raises(ValueError):
        pack_rows(dense, 1, 2)  # 4 overfull rows > 2 table slots
    pack_rows(dense, 8, 2)  # fits in slots, table untouched


@pytest.mark.parametrize("seed", range(3))
def test_from_dense_matches_pack(seed):
    """The traced repack (rsd_from_dense) and the host pack agree after
    densify — including rows routed through the overflow table."""
    rng = random.Random(seed)
    dense = _random_dense_dist(rng, density=0.35)
    for cap in (1, 2, 8):
        a = pack_rows(dense, cap, 64)
        b = rsd_from_dense(jnp.asarray(dense), cap, 64)
        np.testing.assert_array_equal(np.asarray(rsd_to_dense(a)),
                                      np.asarray(rsd_to_dense(b)))
        assert int(b.lost) == 0


@pytest.mark.parametrize("seed", range(3))
def test_gather_scatter_round_trip(seed):
    """Row gather equals a dense row take (via slots AND the table), and a
    full-row scatter-back is an exact overwrite — shrink-safe."""
    rng = random.Random(seed)
    q, n, k, f = 2, 10, 3, 4
    dense = _random_dense_dist(rng, q, n, k, density=0.3)
    sd = _dev(pack_rows(dense, 2, 64))  # tiny cap: rows live in the table
    rows = jnp.asarray([[1, 3, 5, 7], [0, 2, 5, 9]], jnp.int32)
    slab = rsd_gather_rows(sd, rows)
    want = jnp.asarray(dense)[jnp.arange(q)[:, None], rows]
    np.testing.assert_array_equal(np.asarray(slab), np.asarray(want))
    # mutate the slab, scatter back, densify: only the touched rows move
    slab2 = jnp.where(slab > NEG_INF, slab + 1.0, slab)
    rowmask = jnp.asarray([[True, True, False, True], [True] * 4])
    sd2 = rsd_scatter_rows(sd, rows, rowmask, slab2)
    want_d = dense.copy()
    for qi in range(q):
        for fi in range(f):
            if bool(rowmask[qi, fi]):
                r = int(rows[qi, fi])
                want_d[qi, r] = np.where(dense[qi, r] > NEG_INF,
                                         dense[qi, r] + 1.0, dense[qi, r])
    np.testing.assert_array_equal(np.asarray(rsd_to_dense(sd2)), want_d)
    assert int(sd2.lost) == 0


def _scatter_rows_by_element(sd, rows, rowmask, slab):
    """Reference write-back: clear every valid row, then place each finite
    slab entry at its cumsum rank with one index tuple per slab element
    (O(Q·F·N·K) scattered updates). ``rsd_scatter_rows`` must store the
    same state while writing whole rows."""
    q, f, n, k = slab.shape
    e = n * k
    c = sd.idx.shape[2]
    r = sd.ovf_rows.shape[0]
    flat = slab.reshape(q, f, e)
    finite = flat > NEG_INF
    counts = jnp.sum(finite, axis=-1)
    fits = counts <= c
    lane = jnp.arange(q)[:, None]
    key = lane * n + rows
    match = key[..., None] == sd.ovf_rows
    in_ovf, oslot = jnp.any(match, axis=-1), jnp.argmax(match, axis=-1)
    new_claim = rowmask & ~fits & ~in_ovf
    crank = (jnp.cumsum(new_claim.reshape(-1)) - 1).reshape(q, f)
    dest = jnp.where(in_ovf, oslot, sd.ovf_ptr + crank)
    write_ovf = rowmask & (in_ovf | ~fits)
    dest = jnp.where(write_ovf, jnp.minimum(dest, r), r)
    ovf_rows2 = sd.ovf_rows.at[dest].set(key, mode="drop")
    ovf_ts2 = sd.ovf_ts.at[dest].set(flat, mode="drop")
    n_new = jnp.sum(new_claim).astype(jnp.int32)
    dropped = jnp.sum(new_claim & (sd.ovf_ptr + crank >= r)).astype(jnp.int32)
    clear_row = jnp.where(rowmask, rows, n)
    ts1 = sd.ts.at[lane, clear_row].set(NEG_INF, mode="drop")
    write_slots = rowmask & fits & ~in_ovf
    srow = jnp.where(write_slots, rows, n)[:, :, None]
    rank = jnp.cumsum(finite, axis=-1) - 1
    pos = jnp.where(finite & fits[:, :, None], rank, c)
    cols = jnp.broadcast_to(jnp.arange(e, dtype=jnp.int32), (q, f, e))
    lane3 = lane[:, :, None]
    idx2 = sd.idx.at[lane3, srow, pos].set(cols, mode="drop")
    ts2 = ts1.at[lane3, srow, pos].set(flat, mode="drop")
    return RowSparseDist(idx2, ts2, ovf_rows2, ovf_ts2,
                         jnp.minimum(sd.ovf_ptr + n_new, r),
                         sd.lost + dropped)


def _row_with(rng, e, count):
    """One flattened row with ``count`` finite entries at random columns."""
    row = np.full(e, NEG_INF, np.float32)
    row[rng.choice(e, count, replace=False)] = rng.integers(1, 60, count)
    return row


# case -> (stored row counts, slab row counts, dist_cap of the stored
# rows, ovf_cap, share of slab slots masked); counts are (lo, hi) inclusive
_SCATTER_CASES = {
    "fit": ((0, 4), (0, 4), 4, 16, 0.0),
    "shrink": ((4, 4), (0, 2), 4, 16, 0.0),
    "at_cap": ((0, 3), (4, 4), 4, 16, 0.0),
    "newly_over": ((0, 4), (5, 9), 4, 16, 0.0),
    "in_table": ((5, 9), (0, 9), 4, 64, 0.0),
    "masked": ((0, 6), (0, 6), 4, 32, 0.5),
    "table_full": ((0, 5), (5, 9), 4, 3, 0.0),
}


@pytest.mark.parametrize("case", sorted(_SCATTER_CASES))
@pytest.mark.parametrize("seed", range(3))
def test_scatter_rows_matches_element_scatter(case, seed):
    """Row-granular write-back stores exactly what the element scatter
    stored: same ts, overflow table, cursor and lost count, and the same
    idx in every occupied slot (free slots' idx may differ)."""
    stored, fresh, cap, ovf_cap, masked = _SCATTER_CASES[case]
    rng = np.random.default_rng(1000 * seed + sorted(_SCATTER_CASES).index(case))
    q, n, k, f = 3, 12, 2, 5
    e = n * k
    dense = np.full((q, n, e), NEG_INF, np.float32)
    for qi in range(q):
        for x in range(n):
            dense[qi, x] = _row_with(rng, e, rng.integers(stored[0],
                                                          stored[1] + 1))
    if case == "table_full":  # leave one table slot: later claims are lost
        dense[:, :, :] = np.where(np.arange(e) < cap, dense, NEG_INF)
        dense[0, 0] = _row_with(rng, e, cap + 2)
        dense[0, 1] = _row_with(rng, e, cap + 2)
    sd = _dev(pack_rows(dense.reshape(q, n, n, k), cap, ovf_cap))
    rows = np.stack([np.sort(rng.choice(n, f, replace=False))
                     for _ in range(q)]).astype(np.int32)
    rowmask = rng.random((q, f)) >= masked
    if masked:
        rows[0, 0], rowmask[0, 0] = 0, True   # a valid row 0 beside padding
        rowmask[0, 1:3] = False
    rows = np.where(rowmask, rows, 0).astype(np.int32)   # pack_frontier pads 0
    slab = np.stack([np.stack([_row_with(rng, e, rng.integers(fresh[0],
                                                              fresh[1] + 1))
                               for _ in range(f)]) for _ in range(q)])
    args = (sd, jnp.asarray(rows), jnp.asarray(rowmask),
            jnp.asarray(slab.reshape(q, f, n, k)))
    want = jax.device_get(_scatter_rows_by_element(*args))
    got = jax.device_get(rsd_scatter_rows(*args))
    for name in ("ts", "ovf_rows", "ovf_ts", "ovf_ptr", "lost"):
        np.testing.assert_array_equal(getattr(got, name), getattr(want, name),
                                      err_msg=name)
    occupied = want.ts > NEG_INF
    np.testing.assert_array_equal(got.idx[occupied], want.idx[occupied])
    if case == "table_full":
        assert int(got.lost) > 0
    if case in ("newly_over", "in_table"):
        assert int(got.ovf_ptr) > 0


def _scatter_index_tuples(module_text):
    """Index tuples of every ``stablehlo.scatter`` in a lowered module."""
    out = []
    pat = re.compile(r'"stablehlo\.scatter".*?index_vector_dim = (\d+).*?'
                     r'\}\) : \(tensor<[^>]*>, tensor<([0-9x]*)x?i\d+>',
                     re.S)
    for m in pat.finditer(module_text):
        ivd = int(m.group(1))
        dims = [int(d) for d in m.group(2).split("x") if d]
        total = int(np.prod(dims, dtype=np.int64))
        out.append(total // dims[ivd] if ivd < len(dims) else total)
    return out


def test_scatter_rows_writes_whole_rows():
    """No scatter of the lowered write-back has more than Q·F·C index
    tuples: the per-element write (Q·F·N·K tuples) cannot come back."""
    q, f, n, k, c, r = 3, 8, 64, 4, 8, 16
    s = jax.ShapeDtypeStruct
    i32, f32 = jnp.int32, jnp.float32
    args = (RowSparseDist(s((q, n, c), i32), s((q, n, c), f32),
                          s((r,), i32), s((r, n * k), f32), s((), i32),
                          s((), i32)),
            s((q, f), i32), s((q, f), jnp.bool_), s((q, f, n, k), f32))
    tuples = _scatter_index_tuples(
        jax.jit(rsd_scatter_rows).lower(*args).as_text())
    assert len(tuples) == 4, tuples   # idx, ts, ovf_rows, ovf_ts
    assert max(tuples) <= q * f * c, tuples
    # the reading sees the element scatters of the reference
    ref = _scatter_index_tuples(
        jax.jit(_scatter_rows_by_element).lower(*args).as_text())
    assert max(ref) == q * f * n * k, ref


def test_seed_gathered_matches_dense_seed():
    rng = random.Random(0)
    q, n, k, b = 3, 9, 4, 5
    dense = _random_dense_dist(rng, q, n, k, density=0.25)
    sd = _dev(pack_rows(dense, 2, 256))
    src = jnp.asarray(rng.sample(range(n), b), jnp.int32)
    smask = jnp.asarray([True, True, False, True, False])
    qmask = jnp.asarray([True, False, True])
    got = rsd_seed_gathered(sd, src, smask, qmask)
    want = frontier_seed(jnp.asarray(dense), src, smask, qmask)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_valid_pairs_matches_dense():
    """The sparse emit — O(Q·N·dist_cap) instead of the O(Q·N²·K) dense
    scan — produces the identical (Q, N, N) valid-pair set, and the
    pytree-dispatch in batched_valid_pairs routes to it."""
    rng = random.Random(1)
    q, n, k = 3, 9, 4
    dense = _random_dense_dist(rng, q, n, k, density=0.25)
    sd = _dev(pack_rows(dense, 2, 256))
    finals = jnp.asarray(np.random.default_rng(0).random((q, k)) < 0.5)
    low = jnp.asarray([3.0, 10.0, 25.0], jnp.float32)
    want = batched_valid_pairs(jnp.asarray(dense), finals, low)
    np.testing.assert_array_equal(
        np.asarray(rsd_valid_pairs(sd, finals, low)), np.asarray(want))
    np.testing.assert_array_equal(
        np.asarray(batched_valid_pairs(sd, finals, low)), np.asarray(want))


def test_grow_repack_drains_table():
    rng = random.Random(2)
    dense = _random_dense_dist(rng, density=0.35)
    sd = _dev(pack_rows(dense, 1, 64))
    assert int(sd.ovf_ptr) > 0
    need = int(np.asarray(jax.device_get(jnp.max(rsd_row_counts(sd)))))
    cap = 1
    while cap < need:
        cap *= 2
    sd2 = rsd_grow_repack(sd, cap, 64)
    assert int(sd2.ovf_ptr) == 0  # every row now fits its slots
    np.testing.assert_array_equal(np.asarray(rsd_to_dense(sd2)), dense)


# -- unit: gather kernel vs naive oracle ------------------------------------


@pytest.mark.parametrize("seed", range(3))
def test_rowsparse_gather_matches_naive(seed):
    rng = np.random.default_rng(seed)
    m, c, e = 12, 4, 30
    idx = rng.integers(0, e, (m, c)).astype(np.int32)
    ts = np.where(rng.random((m, c)) < 0.6,
                  rng.integers(1, 40, (m, c)).astype(np.float32), NEG_INF)
    want = rowsparse_gather_naive(jnp.asarray(idx), jnp.asarray(ts), e)
    got_ref = rowsparse_gather_ref(jnp.asarray(idx), jnp.asarray(ts), e)
    np.testing.assert_array_equal(np.asarray(got_ref), np.asarray(want))
    got_pl = rowsparse_gather(jnp.asarray(idx), jnp.asarray(ts), e,
                              use_pallas=True, interpret=True)
    np.testing.assert_array_equal(np.asarray(got_pl), np.asarray(want))


# -- stream conformance: dense vs row-sparse --------------------------------


def _random_events(rng, n_vertices, n_edges, t_max, deletions=True):
    ts = sorted(rng.sample(range(1, t_max), k=min(n_edges, t_max - 1)))
    live = {}
    events = []
    for t in ts:
        u, v = rng.randrange(n_vertices), rng.randrange(n_vertices)
        lab = rng.choice(LABELS)
        if deletions and live and rng.random() < 0.15:
            du, dv, dl = rng.choice(sorted(live))
            del live[(du, dv, dl)]
            events.append(("-", du, dv, dl, float(t)))
        else:
            live[(u, v, lab)] = t
            events.append(("+", u, v, lab, float(t)))
    return events


def _specs(rng, n_queries, window):
    specs = []
    for qi in range(n_queries):
        expr = rng.choice(QUERIES)
        dfa = compile_query(expr)
        semantics = "simple" if (dfa.has_containment_property
                                 and rng.random() < 0.4) else "arbitrary"
        specs.append(RegisteredQuery(f"q{qi}", dfa, window, semantics))
    return specs


def _drive(make_engine, events, slide, n_queries):
    g = make_engine()
    next_exp = slide
    out = []
    for (op, u, v, lab, t) in events:
        if t >= next_exp:
            g.expire(t)
            while next_exp <= t:
                next_exp += slide
        if op == "+":
            fresh = g.insert(u, v, lab, t)
            out.append(("+",) + tuple(
                frozenset(fresh[qi]) for qi in range(n_queries)))
        else:
            inv = g.delete(u, v, lab, t)
            out.append(("-",) + tuple(
                frozenset(inv[qi]) for qi in range(n_queries)))
    return g, out


def _assert_streams_equal(tag, dense, sparse):
    assert len(dense) == len(sparse)
    for i, (d, s) in enumerate(zip(dense, sparse)):
        assert d == s, (tag, i, d, s)


BACKENDS = {
    "jnp": lambda: "jnp",
    "pallas": lambda: PallasBackend(interpret=True),
    "bucket": lambda: BucketBackend(n_levels=6, use_pallas=False),
}


def _conformance(seed, make_executor, backend_key, frontier,
                 dist_kwargs=None, batch_size=1, n_slots=24):
    rng = random.Random(seed)
    window = rng.choice([10.0, 25.0])
    nq = 3
    specs = _specs(rng, nq, window)
    events = _random_events(rng, 14, 80, 70)
    fr = dict(frontier=frontier, frontier_cap=4) if frontier else {}
    dist_kwargs = {"dist_layout": "row_sparse", "dist_cap": 4,
                   **(dist_kwargs or {})}

    def dense():
        ex = make_executor(BACKENDS[backend_key](), **fr)
        return BatchedDenseRPQEngine(specs, n_slots=n_slots,
                                     batch_size=batch_size, executor=ex)

    def sparse():
        ex = make_executor(BACKENDS[backend_key](), **fr, **dist_kwargs)
        return BatchedDenseRPQEngine(specs, n_slots=n_slots,
                                     batch_size=batch_size, executor=ex)

    g_d, ev_d = _drive(dense, events, 5.0, nq)
    g_s, ev_s = _drive(sparse, events, 5.0, nq)
    tag = (seed, backend_key, frontier)
    _assert_streams_equal(tag, ev_d, ev_s)
    assert g_d.retained_edges() == g_s.retained_edges(), tag
    assert g_s.executor.dist_stats["lost"] == 0, tag
    return g_d, g_s


def _local(backend, **kw):
    return LocalExecutor(backend, **kw)


def _mesh(backend, **kw):
    return MeshExecutor(model_axis=2, backend=backend, **kw)


@pytest.mark.parametrize("backend_key", sorted(BACKENDS))
@pytest.mark.parametrize("frontier", [None, "auto"])
def test_row_sparse_matches_dense_local(backend_key, frontier):
    _conformance(0, _local, backend_key, frontier)


@pytest.mark.parametrize("backend_key", sorted(BACKENDS))
def test_row_sparse_matches_dense_mesh(backend_key):
    _conformance(1, _mesh, backend_key, None)


def test_row_sparse_matches_dense_mesh_frontier():
    _conformance(2, _mesh, "jnp", "auto")


def test_overflow_table_regression():
    """dist_cap=1 + a small overflow table: most rows overflow, the host
    budget forces drains, drains force ×2 growth re-packs — and the
    stream stays bit-identical throughout with nothing lost."""
    _, g_s = _conformance(
        3, _local, "jnp", None,
        dist_kwargs=dict(dist_cap=1, dist_ovf_cap=512), batch_size=4)
    st = g_s.executor.dist_stats
    assert st["drains"] > 0, st
    assert st["repacks"] > 0, st
    assert st["dist_cap"] > 1, st  # grew toward the live max row occupancy
    assert st["lost"] == 0, st
    assert st["live_entries"] is not None and st["live_entries"] > 0, st


def test_overflow_table_regression_frontier_mesh():
    _, g_s = _conformance(
        4, _mesh, "jnp", "auto",
        dist_kwargs=dict(dist_cap=1, dist_ovf_cap=512), batch_size=4)
    assert g_s.executor.dist_stats["lost"] == 0


def test_survives_slot_growth_and_compaction():
    """More distinct vertices than n_slots: the engine compacts and grows
    the vertex axis mid-stream; the row-sparse re-pack rides
    executor.grow through the canonical dense slab."""
    _conformance(5, _local, "jnp", None, n_slots=8, batch_size=2)


def test_survives_query_churn():
    """Register a query mid-stream and deregister another: lane lifecycle
    re-pads device state in place; the sparse layout rides along
    bit-identically."""
    rng = random.Random(6)
    specs = _specs(rng, 2, 20.0)
    head = _random_events(rng, 10, 40, 35)
    tail = _random_events(random.Random(7), 10, 30, 35)
    late = RegisteredQuery("late", compile_query("a . b*"), 20.0, "arbitrary")

    def run(layout):
        kw = (dict(dist_layout="row_sparse", dist_cap=2)
              if layout == "row_sparse" else {})
        g = BatchedDenseRPQEngine(
            specs, n_slots=16, batch_size=2,
            executor=LocalExecutor("jnp", **kw))
        _, ev = [g, []]
        out = []
        for (op, u, v, lab, t) in head:
            if op == "+":
                out.append(("+", tuple(map(frozenset, g.insert(u, v, lab, t)))))
            else:
                out.append(("-", tuple(map(frozenset, g.delete(u, v, lab, t)))))
        out.append(("reg", frozenset(g.register_query(late))))
        g.deregister_query("q0")
        for (op, u, v, lab, t) in tail:
            t2 = t + 35.0
            if op == "+":
                out.append(("+", tuple(map(frozenset, g.insert(u, v, lab, t2)))))
            else:
                out.append(("-", tuple(map(frozenset, g.delete(u, v, lab, t2)))))
        return out

    assert run("dense") == run("row_sparse")


# -- checkpoints across layouts --------------------------------------------


def _ckpt_state(g):
    return {k: np.asarray(jax.device_get(v))
            for k, v in g.state_arrays().items()}


@pytest.mark.parametrize("src_layout,dst_layout",
                         [("dense", "row_sparse"), ("row_sparse", "dense")])
def test_checkpoint_cross_layout(src_layout, dst_layout):
    rng = random.Random(7)
    specs = _specs(rng, 2, 20.0)
    events = _random_events(rng, 10, 50, 45)

    def make(layout):
        kw = (dict(dist_layout="row_sparse", dist_cap=2)
              if layout == "row_sparse" else {})
        return BatchedDenseRPQEngine(specs, n_slots=16, batch_size=2, **kw)

    g_src, _ = _drive(lambda: make(src_layout), events, 5.0, 2)
    state = _ckpt_state(g_src)
    assert state["dist"].ndim == 4, "checkpoints are canonical dense"
    g_dst = make(dst_layout)
    g_dst.load_state_arrays(state)
    g_dst.load_interner(g_src.interner_state())  # slot map rides alongside
    np.testing.assert_array_equal(
        np.asarray(jax.device_get(g_src.executor.dense_dist())),
        np.asarray(jax.device_get(g_dst.executor.dense_dist())))
    if dst_layout == "row_sparse":
        assert isinstance(g_dst.executor.arrays.dist, RowSparseDist)
    # the restored engine continues the stream identically to the source
    tail = _random_events(random.Random(8), 10, 20, 45)

    def cont(g):
        out = []
        for (op, u, v, lab, t) in tail:
            t2 = t + 45.0
            if op == "+":
                out.append(tuple(map(frozenset, g.insert(u, v, lab, t2))))
            else:
                out.append(tuple(map(frozenset, g.delete(u, v, lab, t2))))
        return out

    assert cont(g_src) == cont(g_dst)


# -- telemetry + validation --------------------------------------------------


def test_dist_stats_telemetry():
    g_d, g_s = _conformance(8, _local, "jnp", None)
    st = g_s.executor.dist_stats
    assert st["layout"] == "row_sparse"
    assert st["dist_cap"] >= 1 and st["ovf_cap"] >= 1
    assert st["dist_bytes"] > 0 and st["slot_cells"] > 0
    # the per-row slabs are O(Q·N·dist_cap) — N-linear, not N² (the fixed
    # bounded overflow table can dominate at toy scale; the N² memory win
    # is benchmarks/fig19_sparse_dist.py's big-N claim)
    q, n, _, k = g_s.executor.dist_shape
    assert st["slot_cells"] == q * n * st["dist_cap"]
    dense_st = g_d.executor.dist_stats
    assert dense_st["layout"] == "dense"


def test_layout_validation():
    with pytest.raises(ValueError):
        LocalExecutor("jnp", dist_layout="bogus")
    with pytest.raises(ValueError):
        LocalExecutor("jnp", dist_layout="row_sparse", dist_cap=0)
    with pytest.raises(ValueError):
        PersistentQueryService(window=1.0, slide=1.0, dist_layout="bogus")


def test_service_dist_log():
    from repro.streaming.generators import so_like, with_deletions
    from repro.streaming.stream import Stream

    tuples = list(with_deletions(so_like(20, 80, seed=3), ratio=0.05, seed=5))

    def run(layout):
        svc = PersistentQueryService(window=20.0, slide=2.0,
                                     dist_layout=layout, dist_cap=2)
        svc.register("q", "a2q . c2a*", engine="dense", n_slots=32)
        svc.ingest(Stream(tuples))
        return svc

    svc_d, svc_s = run("dense"), run("row_sparse")
    assert svc_d.results("q") == svc_s.results("q")
    assert not svc_d.dist_log
    assert svc_s.dist_log, "row-sparse service logs per-interval dist stats"
    seen, st = svc_s.dist_log[-1]
    assert st["layout"] == "row_sparse" and st["lost"] == 0
