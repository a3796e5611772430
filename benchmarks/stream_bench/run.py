"""Benchmark of the persistent-RPQ service on the chip: one cell, one run.

    python3 benchmarks/stream_bench/run.py --workload so-table2.saturate \
        --seed 12345 --seconds 40 --trace 0

Run from the root of a checkout that holds ``BENCHMARK.json``. The cell
(configuration + traffic mix), its metrics and their readers are found by
name in ``BENCHMARK.json`` and the files beside this script.

A run: check that JAX sees enough TPU chips (otherwise exit 3 with no
result); draw the stream and the arrival schedule from ``--seed``; build
the supervised service, warm the shapes the window may meet, and fill
its live window through the timed path (set-up); offer the measured
window open-loop for ``--seconds``; read the peak device memory; compare
every delivered batch with the plain reference; print the comparison's
numbers beside their limits as the last lines of stderr and one JSON
result as the last line of stdout.
``--trace 1`` runs the window under the profiler with host spans around
the program's layers and reports the per-layer metrics instead of the
end-to-end ones.
"""
import time

T_PROCESS = time.perf_counter()  # set-up is measured from here

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)

import check  # noqa: E402
import driver  # noqa: E402
import spans as spans_mod  # noqa: E402
import tracereduce  # noqa: E402
import workload  # noqa: E402


class CompileCounter:
    """Compiles (persistent-cache misses) and cache loads, from JAX's own
    monitoring events, so that any inside the measured window is seen."""

    MISS = "/jax/compilation_cache/cache_misses"
    HIT = "/jax/compilation_cache/cache_hits"
    BUILD = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.compiles = 0
        self.cache_loads = 0
        self.build_s = 0.0
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(self._on_time)

    def _on_event(self, event: str, **_kw) -> None:
        if event == self.MISS:
            self.compiles += 1
        elif event == self.HIT:
            self.cache_loads += 1

    def _on_time(self, event: str, duration: float, **_kw) -> None:
        if event == self.BUILD:
            self.build_s += duration

    def snapshot(self):
        return self.compiles, self.cache_loads


def _cache_entries(cache_dir: str) -> set:
    """Names of the programs in the persistent cache (without hashes)."""
    if not os.path.isdir(cache_dir):
        return set()
    return {n.split("-", 1)[0] for n in os.listdir(cache_dir)}


def _peak(devices, chips: int) -> int:
    """Peak device memory on the fullest chip so far."""
    return int(max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devices[:chips]))


def _caps(group) -> dict:
    """The executor's traced-shape capacities: a change inside the
    window means a program compiled there."""
    ex = group.executor
    return {"frontier_cap": ex.frontier_cap, "ell_cap": ex.ell_cap,
            "dist_cap": ex.dist_cap, "n_slots": group.n_slots}


def load_reader(name: str):
    path = workload.find_metric_reader(name)
    if path is None:
        raise workload.CellError(f"metric {name!r} has no reader "
                                 f"metrics/{name}.py")
    spec = importlib.util.spec_from_file_location(f"metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="copy the traced run's xplane file here")
    ap.add_argument("--override", action="append", default=[],
                    help="KEY=JSON: set a service or registration "
                         "argument (controls and fault tests only)")
    return ap.parse_args(argv)


def check_devices(chips: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise workload.CellError(f"no TPU: JAX found {devices[0].platform}; "
                                 "this benchmark runs on the chip only")
    if len(devices) < chips:
        raise workload.CellError(f"the cell needs {chips} chips, JAX sees "
                                 f"{len(devices)}")
    return devices


def enable_cache() -> str:
    """The program's persistent compilation cache
    (``$JAX_COMPILATION_CACHE_DIR``, else ``.jax_cache/`` in the
    checkout), keeping every program, however quick to build, so that
    only a cell's first run in a checkout compiles. Returns its path."""
    import jax
    from repro.compile_cache import enable_compile_cache

    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def run(args, devices_check=check_devices, bench=None) -> dict:
    """One run; returns the result object (the last stdout line).
    ``devices_check`` and ``bench`` let the tests drive a run on the CPU
    at a small size."""
    if bench is None:
        bench = workload.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell, cfg, traffic = workload.find_cell(bench, args.workload)
    entries = workload.metrics_for(bench, args.workload, bool(args.trace))
    readers = {m["name"]: load_reader(m["name"]) for m in entries}
    devices = devices_check(int(cell["chips"]))
    sys.path.insert(0, os.path.join(ROOT, "src"))
    cache_dir = enable_cache()
    import jax

    clock = CompileCounter()
    overrides = {k: json.loads(v) for k, v in
                 (o.split("=", 1) for o in args.override)}
    offsets, backlog = workload.schedule(traffic, args.seed, args.seconds)
    fill, body = workload.make_stream(cfg, traffic, args.seed, len(offsets))
    fill_sgts, body_sgts = driver.to_sgts(fill), driver.to_sgts(body)

    state_dir = tempfile.mkdtemp(prefix="stream_bench.")
    try:
        h = driver.Harness(cfg, state_dir, overrides)
        h.warm(int(cfg["warm_slot_clears"]))
        t_fill = time.perf_counter()
        h.fill(fill, fill_sgts)
        fill_s = time.perf_counter() - t_fill
        group = h.group
        hooks = None
        trace_dir = os.path.join(state_dir, "trace")
        programs = sorted({p for r in readers.values()
                           for p in getattr(r, "PROGRAMS", ())})
        on_wait = None
        if args.trace:
            hooks = spans_mod.Spans()
            extra = {}
            for r in readers.values():
                extra.update(getattr(r, "SPANS", {}))
            hooks.install({**spans_mod.BASE_SPANS, **extra})
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=opts)

            def on_wait(dt):
                with jax.profiler.TraceAnnotation("bench.wait_due"):
                    time.sleep(dt)

        cached0 = _cache_entries(cache_dir)
        fstats0 = dict(group.executor.frontier_stats)
        caps0 = _caps(group)
        peak_setup = _peak(devices, int(cell["chips"]))
        snapshots0 = h.sup._snapshots
        first_window_batch = len(h.batches)
        c0 = clock.snapshot()
        setup_s = time.perf_counter() - T_PROCESS
        if args.trace:
            with jax.profiler.TraceAnnotation(tracereduce.WINDOW_SPAN):
                win = driver.drive_window(h, body, body_sgts, offsets,
                                          backlog, args.seconds,
                                          on_wait=on_wait)
            jax.profiler.stop_trace()
            hooks.remove()
        else:
            win = driver.drive_window(h, body, body_sgts, offsets, backlog,
                                      args.seconds)
        c1 = clock.snapshot()
        snapshots = h.sup._snapshots - snapshots0
        caps1 = _caps(group)
        compiled_in_window = sorted(_cache_entries(cache_dir) - cached0)
        stats = devices[0].memory_stats() or {}
        peak = _peak(devices, int(cell["chips"]))
        fstats1 = dict(group.executor.frontier_stats)
        live_vertices = len(group.slot_of)
        sup = h.sup
        trips = sup.restarts + len(sup.breaker.log if sup.breaker else [])
        window_batches = [len(b) for b in h.batches[first_window_batch:]]
        batches = h.batches
        results_by_lsn = sup.results_by_lsn
        invalidated_by_lsn = sup.invalidated_by_lsn
        h.close()
        del h, group, sup
        state_bytes = sum(os.path.getsize(os.path.join(d, f))
                          for d, _dirs, files in os.walk(state_dir)
                          for f in files)
        trace = None
        if args.trace:
            path = tracereduce.find_xplane(trace_dir)
            if args.keep_trace:
                os.makedirs(args.keep_trace, exist_ok=True)
                shutil.copy(path, args.keep_trace)
            trace = tracereduce.reduce_file(path, programs)
        delivered = win["delivered"]
        n_due = len(delivered) if not backlog else win["fed"]
        undelivered = sum(1 for d in delivered[:n_due] if d is None)
        t_ref = time.perf_counter()
        numbers, counts = check.compare(
            cfg, batches, results_by_lsn, invalidated_by_lsn,
            first_window_batch, undelivered, trips)
        ref_s = time.perf_counter() - t_ref
    finally:
        shutil.rmtree(state_dir, ignore_errors=True)

    rec = {
        "seconds": args.seconds, "setup_s": setup_s, "window": win,
        "window_batch_sizes": window_batches,
        "spans": hooks.records if hooks else {},
        "frontier": {k: fstats1[k] - fstats0.get(k, 0)
                     for k in ("dispatches", "fallbacks", "delete_dispatches",
                               "rows_relaxed", "dense_row_equiv")
                     if k in fstats1},
        "trace": trace,
    }
    metrics = {}
    for m in entries:
        value = readers[m["name"]].read(rec)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind,
              "count": int(cell["chips"]),
              "memory_peak_bytes": peak}
    if trace is not None:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
    diag = {
        "setup_s": setup_s, "fill_s": fill_s, "fill_events": len(fill),
        "window_s": win["t_close"] - win["t_start"], "fed": win["fed"],
        "compiles_in_window": c1[0] - c0[0],
        "cache_loads_in_window": c1[1] - c0[1],
        "compiled_in_window": compiled_in_window,
        "compiles_total": clock.compiles, "build_s": clock.build_s,
        "cache_loads_total": clock.cache_loads,
        "live_vertices": live_vertices,
        "bytes_in_use": stats.get("bytes_in_use"),
        "memory_peak_bytes_setup": peak_setup,
        "capacities_at_open": caps0, "capacities_at_close": caps1,
        "snapshots_in_window": snapshots,
        "state_dir_bytes": state_bytes,
        "reference_s": ref_s, **counts, "frontier": rec["frontier"],
    }
    out = {
        "correct": check.correct(numbers),
        "attempted": n_due,
        "failed": counts["window_events_failed"],
        "metrics": metrics,
        "device": device,
    }
    if trace is not None and trace.get("device_ops"):
        out["breakdown"] = {"device_ops": trace["device_ops"],
                            "idle_gaps": trace["idle_gaps"]}
    out["diagnostics"] = diag
    out["compared"] = {k: {"value": v, "limit": check.LIMITS[k]}
                       for k, v in numbers.items()}
    return out


def main(argv=None) -> int:
    args = parse_args(argv)
    try:
        out = run(args)
    except workload.CellError as e:
        print(f"stream_bench: {e}", file=sys.stderr)
        return 3
    print(json.dumps(out["diagnostics"], default=str), file=sys.stderr)
    for name, c in out["compared"].items():
        print(f"{name} {c['value']} limit {c['limit']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
