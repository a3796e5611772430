"""A dispatch's result mask crosses to the host row-compacted.

``compact_rows`` gathers, on the device, the rows of a ``(Q, N, N)``
result mask that hold a set bit; the engine copies those rows and maps
them back through their ids, and copies the whole mask only when more
rows are set than were compacted. Either way the decoded cells are the
mask's ``np.nonzero``, in its order, and the ``engine.result_overflows``
counter says which way a fetch went.

The engine test lowers ``RESULT_ROWS`` to 2 so that most dispatches of a
small stream overflow, and checks that its per-call new and invalidated
pairs equal those of the untouched engine and of ``core/reference.py``."""
import contextlib
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import telemetry
from repro.core import compile_query
from repro.core import executor as executor_mod
from repro.core.engine import _fetch_result
from repro.core.executor import RESULT_ROWS, DispatchResult, compact_rows
from repro.core.reference import RAPQ
from repro.streaming.generators import so_like, with_deletions
from repro.streaming.service import PersistentQueryService
from repro.streaming.stream import SGT, Stream

Q, N = 3, 128          # Q·N = 384 rows, more than RESULT_ROWS

#: how many rows (q, x) each case sets, drawn from a seeded rng
ROW_COUNTS = {"no_rows": 0, "one_row": 1, "exactly_r": RESULT_ROWS,
              "r_plus_one": RESULT_ROWS + 1}


def _mask(case: str) -> np.ndarray:
    rng = np.random.default_rng(7)
    if case == "every_lane":
        rows = [q * N + x for q in range(Q)
                for x in rng.choice(N, size=5, replace=False)]
    else:
        rows = rng.choice(Q * N, size=ROW_COUNTS[case], replace=False)
    mask = np.zeros((Q * N, N), bool)
    for r in rows:
        cols = rng.choice(N, size=int(rng.integers(1, 4)), replace=False)
        mask[r, cols] = True
    return mask.reshape(Q, N, N)


def _counted(name: str, t0: int, t1: int) -> float:
    return sum(e.n for e in telemetry.records(name, t0, t1)[0])


@pytest.mark.parametrize("case", [*ROW_COUNTS, "every_lane"])
def test_compact_rows_decodes_as_nonzero(case):
    mask = _mask(case)
    n_set = int(mask.any(-1).sum())
    assert 0 < RESULT_ROWS < Q * N
    if case == "every_lane":
        assert set(np.nonzero(mask.any(-1))[0]) == set(range(Q))

    mask_dev = jnp.asarray(mask)
    result = jax.jit(compact_rows)(mask_dev)
    assert int(result.n_rows) == n_set
    assert result.row_ids.shape == (RESULT_ROWS,)
    assert result.rows.shape == (RESULT_ROWS, N)
    ids = np.asarray(result.row_ids)
    kept = min(n_set, RESULT_ROWS)
    np.testing.assert_array_equal(
        ids[:kept], np.nonzero(mask.reshape(Q * N, N).any(-1))[0][:kept])
    assert (ids[kept:] == -1).all()
    assert not np.asarray(result.rows)[kept:].any()

    t0 = time.perf_counter_ns()
    fetched = _fetch_result(DispatchResult(result, mask_dev))
    t1 = time.perf_counter_ns()
    overflow = n_set > RESULT_ROWS
    assert (fetched.mask is not None) == overflow
    assert _counted("engine.result_overflows", t0, t1) == int(overflow)
    assert _counted("engine.result_fetches", t0, t1) == 1
    for got, want in zip(fetched.nonzero(), np.nonzero(mask)):
        np.testing.assert_array_equal(got, want)


def test_compact_rows_caps_at_the_mask_rows():
    """A mask with fewer rows than ``RESULT_ROWS`` compacts them all and
    never overflows."""
    mask = np.zeros((2, 4, 4), bool)
    mask[1, 3, [0, 2]] = True
    mask_dev = jnp.asarray(mask)
    result = compact_rows(mask_dev)
    assert result.row_ids.shape == (8,)
    assert int(result.n_rows) == 1
    fetched = _fetch_result(DispatchResult(result, mask_dev))
    assert fetched.mask is None
    for got, want in zip(fetched.nonzero(), np.nonzero(mask)):
        np.testing.assert_array_equal(got, want)


# -- the engine, on a stream whose results overflow a lowered RESULT_ROWS ----

#: the Table-2 shapes that do not accept the empty path, over the SO
#: labels (the reference reports no invalidated (x, x) pair of a nullable
#: query where the engine does, with or without compaction)
QUERIES = {
    "Q2": "a2q . c2a*", "Q3": "a2q . c2a* . c2q*", "Q5": "a2q . c2a* . c2q",
    "Q7": "a2q . c2a . c2q*", "Q9": "(a2q | c2a | c2q)+",
    "Q10": "(a2q | c2a | c2q) . c2a*", "Q11": "a2q . c2a . c2q",
}
WINDOW, SLIDE = 4.0, 1.0
N_SLOTS = 64            # 7 lanes · 64 = 448 rows, more than RESULT_ROWS
CALL_EVENTS = 8


def _stream():
    """``so_like`` with 5% deletions, restamped k/8 so every timestamp is
    exact in the engine's float32 clock and the reference's float64."""
    events = with_deletions(so_like(40, 160, seed=3), ratio=0.05, seed=3)
    return [SGT((k + 1) / 8, e.src, e.dst, e.label, e.op)
            for k, e in enumerate(events)]


def _service(frontier):
    # dense layouts: the compaction reads the same (Q, N, N) mask in every
    # layout, and these compile in a fraction of the sparse ones' time
    svc = PersistentQueryService(
        window=WINDOW, slide=SLIDE, executor="local", frontier=frontier,
        frontier_cap=16)
    for name, expr in QUERIES.items():
        svc.register(name, expr, engine="dense", n_slots=N_SLOTS,
                     batch_size=1)
    return svc


def _run(events, frontier):
    svc = _service(frontier)
    calls = []
    for i in range(0, len(events), CALL_EVENTS):
        report = svc.ingest(Stream(events[i:i + CALL_EVENTS]))
        calls.append(({n: report[n] for n in QUERIES},
                      {n: report.invalidated[n] for n in QUERIES}))
    return calls


def _reference(events):
    """One ``RAPQ`` per query, driven as the service drives its reference
    engines: lazy expiry at slide boundaries, a deletion judged at its own
    clock."""
    engines = {n: RAPQ(compile_query(e), WINDOW) for n, e in QUERIES.items()}
    next_expiry = SLIDE
    calls = []
    for i in range(0, len(events), CALL_EVENTS):
        new = {n: set() for n in QUERIES}
        invalidated = {n: set() for n in QUERIES}
        for e in events[i:i + CALL_EVENTS]:
            if e.ts >= next_expiry:
                for eng in engines.values():
                    eng.expire(e.ts)
                while next_expiry <= e.ts:
                    next_expiry += SLIDE
            for n, eng in engines.items():
                if e.op == "+":
                    new[n] |= eng.insert(e.src, e.dst, e.label, e.ts)
                else:
                    eng.expire(e.ts)
                    invalidated[n] |= eng.delete(e.src, e.dst, e.label, e.ts)
        calls.append((new, invalidated))
    return calls


@contextlib.contextmanager
def _result_rows(n: int):
    """``RESULT_ROWS`` set to ``n``: the dispatches read it when they
    trace, so the jit caches are cleared on the way in and out."""
    saved = executor_mod.RESULT_ROWS
    jax.clear_caches()
    executor_mod.RESULT_ROWS = n
    try:
        yield
    finally:
        executor_mod.RESULT_ROWS = saved
        jax.clear_caches()


@pytest.mark.parametrize("frontier", ["on", "off"])
def test_overflowing_results_match_compacted_and_reference(frontier):
    events = _stream()
    t0 = time.perf_counter_ns()
    compacted = _run(events, frontier)
    t1 = time.perf_counter_ns()
    with _result_rows(2):
        overflowing = _run(events, frontier)
    t2 = time.perf_counter_ns()

    assert overflowing == compacted
    assert compacted == _reference(events)
    assert any(any(new.values()) for new, _inv in compacted)
    assert any(any(inv.values()) for _new, inv in compacted)
    assert _counted("engine.result_fetches", t0, t1) > 0
    assert _counted("engine.result_overflows", t0, t1) == 0
    assert _counted("engine.result_overflows", t1, t2) > 0
