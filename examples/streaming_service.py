"""End-to-end driver (the paper's deployment kind): a persistent-query
service ingesting a streaming graph with sliding-window semantics.

* registers a mixed workload (arbitrary + simple path semantics, dense +
  reference engines) over an SO-like stream,
* ingests with eager evaluation / lazy expiration (slide interval beta),
* injects explicit deletions (negative tuples),
* checkpoints engine state mid-stream and proves re-attach works,
* prints per-query result stats, and the service's ingest-call times
  from the in-program recorder (``repro.telemetry``).

    PYTHONPATH=src python examples/streaming_service.py
"""
import tempfile
import time

from repro import telemetry
from repro.compile_cache import enable_compile_cache
from repro.streaming.generators import so_like, with_deletions
from repro.streaming.service import PersistentQueryService
from repro.streaming.stream import Stream


def main() -> None:
    enable_compile_cache()
    stream = with_deletions(so_like(n_vertices=48, n_edges=900, seed=42),
                            ratio=0.02, seed=1)
    print(f"stream: {len(stream)} sgts over {stream.span()[1]:.0f}s "
          f"(2% explicit deletions)")

    svc = PersistentQueryService(window=20.0, slide=2.0)
    svc.register("notify", "a2q . c2a*", engine="dense", n_slots=96)
    svc.register("notify_simple", "a2q . c2a*", engine="dense",
                 path_semantics="simple", n_slots=96)
    svc.register("reach_ref", "(a2q | c2a)+", engine="reference")

    tuples = list(stream)
    half = len(tuples) // 2
    t0 = time.perf_counter()
    svc.ingest(Stream(tuples[:half]))

    # --- mid-stream checkpoint + re-attach (fault tolerance drill) ---------
    with tempfile.TemporaryDirectory() as ckpt_dir:
        svc.snapshot(ckpt_dir, step=half)
        svc2 = PersistentQueryService(window=20.0, slide=2.0)
        svc2.register("notify", "a2q . c2a*", engine="dense", n_slots=96)
        svc2.register("notify_simple", "a2q . c2a*", engine="dense",
                      path_semantics="simple", n_slots=96)
        svc2.register("reach_ref", "(a2q | c2a)+", engine="reference")
        svc2.restore(ckpt_dir)
        assert svc2.results("notify") == svc.results("notify")
        print(f"[ckpt] snapshot + re-attach at sgt {half}: OK "
              f"({len(svc.results('notify'))} results preserved)")

    svc.ingest(Stream(tuples[half:]))
    wall = time.perf_counter() - t0

    print(f"\ningested {len(tuples)} sgts in {wall:.2f}s "
          f"({len(tuples)/wall:.0f} sgts/s aggregate)")
    calls = telemetry.summary()["spans"]["service.ingest"]
    print(f"  service.ingest: {calls['count']} calls, "
          f"p95 {calls['p95_ms']:.1f} ms, max {calls['max_ms']:.1f} ms")
    for name, st in svc.stats.items():
        print(f"  {name:15s} results={st.results:6d} "
              f"conflicted={st.conflicted}")


if __name__ == "__main__":
    main()
