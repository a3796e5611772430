"""Benchmark harness: one module per paper table/figure. Prints
``name,us_per_call,derived`` CSV rows (paper-faithful reference engine AND
the dense TPU engine where applicable) plus the roofline table from the
dry-run artifacts.

Each module's ``run()`` return value is also written as a machine-readable
``benchmarks/results/BENCH_<name>.json`` summary (edges/s, rounds, skip
fractions, frontier occupancy, ... — whatever the module reports), so the
perf trajectory is tracked ACROSS PRs instead of living only in scrollback:
diff two checkouts' BENCH files to see what a change did to throughput.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "results")


def _assert_tracked(path: str, allow_untracked: bool) -> None:
    """A BENCH summary that exists only in a working tree silently drops
    out of the cross-PR perf trajectory (the whole point of the files).
    Fail LOUDLY when the file is not under version control instead of
    letting the next ``git clean`` erase the datapoint."""
    try:
        proc = subprocess.run(
            ["git", "ls-files", "--error-unmatch", os.path.abspath(path)],
            capture_output=True, cwd=os.path.dirname(os.path.abspath(path)))
    except (OSError, FileNotFoundError):
        return  # no git in the environment: nothing to enforce
    if proc.returncode != 0:
        msg = (f"{path}: BENCH summary is not tracked by git — `git add` it "
               "so the perf trajectory keeps the datapoint (or rerun with "
               "--allow-untracked)")
        if allow_untracked:
            print(f"[warn] {msg}")
        else:
            print(f"[error] {msg}")
            raise SystemExit(2)


def _write_summary(name: str, result, allow_untracked: bool = False) -> None:
    """BENCH_<name>.json next to the dry-run artifacts. Non-JSON-able
    leaves (device arrays, engines) degrade to their repr — the summary is
    for trend diffs, not restoration."""
    os.makedirs(RESULTS_DIR, exist_ok=True)
    path = os.path.join(RESULTS_DIR, f"BENCH_{name}.json")
    with open(path, "w") as f:
        json.dump({"name": name, "result": result}, f, indent=1,
                  default=lambda o: repr(o), sort_keys=True)
    _assert_tracked(path, allow_untracked)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="", help="substring filter on module name")
    ap.add_argument("--fast", action="store_true", help="smaller sizes")
    ap.add_argument("--no-summaries", action="store_true",
                    help="skip writing BENCH_*.json result summaries")
    ap.add_argument("--allow-untracked", action="store_true",
                    help="downgrade the untracked-BENCH-summary error to a "
                         "warning (first run of a new figure, scratch trees)")
    ap.add_argument("--check", action="store_true",
                    help="run the dispatch-hygiene analyzer on src/ first "
                         "and refuse to time a dirty tree")
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()

    if args.check:
        # a tree that breaks its own dispatch discipline (host syncs in
        # traced code, un-bucketed capacities — docs/invariants.md) times
        # the wrong program; gate before paying for any compile
        from repro.analysis.analyzer import format_text, run as run_analysis

        repo_src = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "src")
        findings, n_files = run_analysis([repo_src])
        live = [f for f in findings if not f.suppressed]
        if live:
            print(format_text(findings, n_files))
            raise SystemExit(
                f"--check: {len(live)} unsuppressed finding(s); refusing "
                "to benchmark a dirty tree")
        print(f"--check: analyzer clean over {n_files} file(s)")

    from . import (fig4_throughput, fig5_index_size, fig6_window,
                   fig7_query_size, fig10_deletions, fig11_vs_batch,
                   fig12_multi_query, fig13_query_churn,
                   fig14_sharded_engine, fig15_backend_shootout,
                   fig16_frontier, fig17_deletions, fig18_sparse_adjacency,
                   fig19_sparse_dist, fig20_survival, roofline, table4_rspq)

    scale = 0.4 if args.fast else 1.0
    modules = [
        ("fig4", lambda: fig4_throughput.run(n_edges=int(1500 * scale))),
        ("fig5", lambda: fig5_index_size.run(n_edges=int(1500 * scale))),
        ("fig6", lambda: fig6_window.run(n_edges=int(2000 * scale))),
        ("fig7", lambda: fig7_query_size.run(n_edges=int(1200 * scale))),
        ("fig10", lambda: fig10_deletions.run(n_edges=int(1200 * scale))),
        ("table4", lambda: table4_rspq.run(n_edges=int(900 * scale))),
        ("fig11", lambda: fig11_vs_batch.run(n_edges=int(400 * scale))),
        ("fig12", lambda: fig12_multi_query.run(n_edges=int(600 * scale))),
        ("fig13", lambda: fig13_query_churn.run(n_edges=int(450 * scale))),
        # fig14 shards over THIS process's devices (one shard on a bare
        # interpreter; run under XLA_FLAGS=--xla_force_host_platform_device_count=8
        # for the real sharded point — the CI slow tier does)
        ("fig14", lambda: fig14_sharded_engine.run(n_edges=int(400 * scale))),
        # fig15 runs all three contraction backends through both executors
        # (pallas/bucket kernels interpret off-TPU; see the module docstring)
        ("fig15", lambda: fig15_backend_shootout.run(n_edges=int(240 * scale))),
        # fig16: frontier-restricted ingest vs the dense relaxation on
        # sparse low-degree windows (per-event identity asserted inside)
        ("fig16", lambda: fig16_frontier.run(n_edges=int(260 * scale),
                                             executors=("local",))),
        # fig17: cone-restricted incremental deletions vs the dense
        # from-scratch re-derivation (per-event invalidation-set identity
        # asserted inside)
        ("fig17", lambda: fig17_deletions.run(n_edges=int(200 * scale),
                                              executors=("local",))),
        # fig18: padded-ELL adjacency vs the dense (L, N, N) slab — per-stage
        # ingest split at the anchors, ELL-only measured at N=100k where the
        # dense slab is infeasible by construction (identity asserted inside)
        ("fig18", lambda: fig18_sparse_adjacency.run(
            anchors=tuple(int(a * scale) for a in (2048, 4096, 8192)),
            reps=2 if args.fast else 3,
            identity_edges=int(150 * scale))),
        # fig19: row-sparse dist (per-source-row reachable sets + sparse
        # emit) vs the dense (Q, N, N, K) slab — per-stage split at the
        # anchors, sparse-only measured at N=128k where the dense dist is
        # infeasible by construction (identity asserted inside)
        ("fig19", lambda: fig19_sparse_dist.run(
            anchors=tuple(int(a * scale) for a in (2048, 8192)),
            reps=2 if args.fast else 3,
            identity_edges=int(150 * scale))),
        # fig20: supervised service under seeded chaos plans — recovery
        # time, WAL replay throughput, and result-stream identity across
        # injected crashes/stragglers/transients (identity asserted inside)
        ("fig20", lambda: fig20_survival.run(
            n_edges=int(220 * scale),
            seeds=(0,) if args.fast else (0, 1, 2))),
        ("roofline", roofline.run),
    ]
    print("name,us_per_call,derived")
    for name, fn in modules:
        if args.only and args.only not in name:
            continue
        result = fn()
        if not args.no_summaries:
            _write_summary(name, result, args.allow_untracked)


if __name__ == "__main__":
    main()
