"""Chip smoke test: drive the persistent-RPQ service's main path on a TPU.

    python chip_smoke.py              # one chip: exactness + full size
    python chip_smoke.py --chips 4    # the Q-sharded mesh path vs local

The path is the one users call: ``ServiceSupervisor`` (WAL + snapshots)
-> ``PersistentQueryService`` -> ``BatchedDenseRPQEngine`` -> executor ->
contraction backend, with the SO deployment's configuration (frontier
``auto``, ELL adjacency, row-sparse dist) and all eleven Table-2 queries.

One chip:
  1. device check: the first JAX device must be a TPU; there is no CPU
     fallback, and nothing is printed as a result without one;
  2. exactness: a small SO-shaped stream with ~2% deletions; every query's
     per-batch result and invalidation stream must equal the paper's
     reference engines (``core/reference.py``) fed the same batches;
  3. full size: the largest vertex capacity the frontier dispatch fits
     in one chip's HBM, a stream whose live window interns close to it,
     run once with ``backend="jnp"`` and once with ``backend="pallas"``;
     the two result streams must be identical.

``--chips 4``: only the mesh path: the same service Q-sharded over four
chips (``executor="mesh"``) against ``executor="local"`` on one chip, in
this one process; the result streams must be identical.

Any restart, recovery or circuit-breaker trip fails the run (no fault
plan is installed). The last line of stdout is one JSON object naming the
device; it is printed only when every phase passed.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

#: the SO deployment's layout: frontier-restricted ingest whose capacity
#: grows on overflow, padded-ELL adjacency, row-sparse dist
SO_LAYOUT = dict(frontier="auto", adj_layout="ell", dist_layout="row_sparse")

#: exactness phase: small enough for the pointer-based reference engines
SMALL = dict(n_vertices=400, n_edges=2000, window=10.0, slide=1.0,
             n_slots=256, frontier_cap=128, ell_cap=8, dist_cap=64)

#: full-size phase. n_slots is the largest power of two whose frontier
#: ingest and delete dispatches fit one v5e's 16 GB (tests/
#: test_tpu_compile.py compiles them; 2048 needs 23.6 GB in one buffer).
#: The stream's live window interns at most ~925 vertices, so the vertex
#: axis never grows. Starting capacities are the ones this stream's window
#: reaches, so the run compiles each dispatch once. The event count keeps
#: both backends' runs (~0.13 s per event on a v5e) inside the time limit.
FULL = dict(n_vertices=3000, n_edges=1500, window=60.0, slide=6.0,
            n_slots=1024, frontier_cap=64, ell_cap=16, dist_cap=64)

#: four-chip phase: the mesh path densifies the sparse layouts per
#: dispatch, so it runs at a vertex capacity its dense slabs fit
#: (the live window interns at most ~190 vertices)
MESH = dict(n_vertices=600, n_edges=300, window=12.0, slide=1.2,
            n_slots=256, frontier_cap=64, ell_cap=16, dist_cap=64)

BATCH_EVENTS = 8


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def device_summary(devices) -> dict:
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (its own
    monitoring events), so compile time is reported apart from the run."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax

        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_kw) -> None:
        if event in self.EVENTS:
            self.seconds += duration


def make_stream(cfg: dict, seed: int):
    from repro.streaming.generators import so_like, with_deletions

    return list(with_deletions(
        so_like(cfg["n_vertices"], cfg["n_edges"], seed=seed),
        ratio=0.02, seed=seed))


def service_factory(cfg: dict, engine: str, backend: str = "jnp",
                    executor: str = "local"):
    """A pure ``make_service`` for the supervisor: a fresh service with
    every Table-2 query registered (the supervisor rebuilds through it)."""
    from benchmarks.common import so_queries
    from repro.streaming.service import PersistentQueryService

    caps = {k: cfg[k] for k in ("frontier_cap", "ell_cap", "dist_cap")
            if k in cfg}

    def make(**overrides):
        kw = dict(window=cfg["window"], slide=cfg["slide"],
                  executor=executor, **SO_LAYOUT, **caps)
        kw.update(overrides)
        svc = PersistentQueryService(**kw)
        for name, expr in so_queries().items():
            if engine == "dense":
                svc.register(name, expr, n_slots=cfg["n_slots"],
                             backend=backend)
            else:
                svc.register(name, expr, engine="reference")
        return svc

    return make


def supervised_run(make, stream, ckpt_every: int) -> dict:
    """Run ``stream`` through a fresh supervisor (WAL + async snapshots +
    circuit breaker) and return its streams and counters."""
    from repro.streaming.supervisor import CircuitBreaker, ServiceSupervisor

    with tempfile.TemporaryDirectory() as d:
        sup = ServiceSupervisor(make, d, batch_events=BATCH_EVENTS,
                                ckpt_every=ckpt_every,
                                breaker=CircuitBreaker())
        t0 = time.perf_counter()
        try:
            sup.run(stream)
        finally:
            sup.wal.close()
        wall = time.perf_counter() - t0
    check(sup.restarts == 0 and not sup.recoveries,
          f"{sup.restarts} restart(s) without a fault plan")
    check(not sup.breaker.log,
          f"circuit breaker tripped: {sup.breaker.log}")
    return {"sup": sup, "wall": wall,
            "results": [r for _lsn, r in sup.result_stream()],
            "invalidated": [r for _lsn, r in sup.invalidation_stream()]}


def phase_exactness(seed: int) -> None:
    """Dense service under the supervisor vs the reference engines."""
    from repro.streaming.stream import Stream

    stream = make_stream(SMALL, seed)
    dense = supervised_run(service_factory(SMALL, "dense"), stream,
                           ckpt_every=4)
    ref_svc = service_factory(SMALL, "reference")()
    ref_new, ref_inv = [], []
    for i in range(0, len(stream), BATCH_EVENTS):
        rep = ref_svc.ingest(Stream(stream[i:i + BATCH_EVENTS]))
        ref_new.append({k: frozenset(v) for k, v in rep.items()})
        ref_inv.append({k: frozenset(v) for k, v in rep.invalidated.items()})
    check(len(dense["results"]) == len(ref_new),
          f"{len(dense['results'])} dense batches vs {len(ref_new)}")
    for i, (d, r) in enumerate(zip(dense["results"], ref_new)):
        check(d == r, f"batch {i}: result stream differs from the reference")
    for i, (d, r) in enumerate(zip(dense["invalidated"], ref_inv)):
        check(d == r,
              f"batch {i}: invalidation stream differs from the reference")
    n_res = sum(len(v) for b in ref_new for v in b.values())
    n_inv = sum(len(v) for b in ref_inv for v in b.values())
    n_del = sum(1 for s in stream if s.op == "-")
    print(f"[exactness] {len(stream)} events ({n_del} deletions), "
          f"{len(ref_new)} batches, {n_res} result pairs, {n_inv} "
          f"invalidated pairs: identical to the reference engines")


def _live_counts(sup) -> tuple:
    import jax

    from repro.core.sparse_adj import ell_live_edges

    group = sup.service._group
    edges = int(jax.device_get(ell_live_edges(group.executor.arrays.adj)))
    return len(group.slot_of), edges, group.n_slots


def phase_full_size(seed: int, clock: CompileClock) -> None:
    """jnp vs pallas at the full vertex capacity."""
    import jax

    stream = make_stream(FULL, seed)
    runs = {}
    for backend in ("jnp", "pallas"):
        c0 = clock.seconds
        run = supervised_run(service_factory(FULL, "dense", backend=backend),
                             stream, ckpt_every=256)
        compile_s = clock.seconds - c0
        live_v, live_e, n_slots = _live_counts(run["sup"])
        check(n_slots == FULL["n_slots"],
              f"vertex axis grew to {n_slots} (live window too large)")
        peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
        ex = run["sup"].service._group.executor
        print(f"[full-size {backend}] n_slots={n_slots} live vertices="
              f"{live_v} live edges={live_e} events={len(stream)} "
              f"frontier_cap={ex.frontier_cap} ell_cap={ex.ell_cap} "
              f"dist_cap={ex.dist_cap}")
        print(f"[full-size {backend}] peak_bytes_in_use={peak} "
              f"compile_s={compile_s:.1f} wall_s={run['wall']:.1f}")
        print(f"[full-size {backend}] host-clock smoke figure, not a "
              f"benchmark metric: {len(stream) / run['wall']:.1f} events/s "
              f"including compile, "
              f"{len(stream) / max(run['wall'] - compile_s, 1e-9):.1f} "
              f"events/s excluding it")
        runs[backend] = (run["results"], run["invalidated"])
        del run
    check(runs["jnp"] == runs["pallas"],
          "jnp and pallas result streams differ at full size")
    n_res = sum(len(v) for b in runs["jnp"][0] for v in b.values())
    print(f"[full-size] jnp == pallas: {len(runs['jnp'][0])} batches, "
          f"{n_res} result pairs")


def phase_mesh(seed: int, devices) -> None:
    """Q-sharded mesh executor over four chips vs the local executor."""
    stream = make_stream(MESH, seed)
    runs = {}
    for executor in ("mesh", "local"):
        run = supervised_run(
            service_factory(MESH, "dense", executor=executor), stream,
            ckpt_every=256)
        live_v, live_e, n_slots = _live_counts(run["sup"])
        in_use = [(d.memory_stats() or {}).get("bytes_in_use") for d in devices]
        print(f"[mesh {executor}] n_slots={n_slots} live vertices={live_v} "
              f"live edges={live_e} events={len(stream)} "
              f"wall_s={run['wall']:.1f} bytes_in_use per chip={in_use}")
        runs[executor] = (run["results"], run["invalidated"])
        del run
    check(runs["mesh"] == runs["local"],
          "mesh and local result streams differ")
    print(f"[mesh] mesh == local over {len(devices)} chips: "
          f"{len(runs['mesh'][0])} batches")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform}); "
              "this smoke test runs on the chip only", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2

    from repro.compile_cache import enable_compile_cache

    print(f"[device] {device_summary(devices)} compile cache: "
          f"{enable_compile_cache()}")
    clock = CompileClock()
    try:
        if args.chips == 4:
            phase_mesh(args.seed, devices[:4])
        else:
            phase_exactness(args.seed)
            phase_full_size(args.seed, clock)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device_summary(devices)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
