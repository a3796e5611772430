"""The harness, driven on the CPU at a small size (``testdata/``) with
the chip check skipped: a sound run is correct, and the control and each
fault the cells can have (``faults.py``) make ``correct`` come out false.
The control is the program's own lower-precision path, ``backend
"mxu_bucket"`` (timestamps quantized to 8 levels per window), in place
of the exact float32 one. Each case takes about half a minute.

    PYTHONPATH=src python -m pytest -q benchmarks/stream_bench"""
import copy
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [HERE, os.path.join(ROOT, "src")]

import faults  # noqa: E402
import run  # noqa: E402
import workload  # noqa: E402

SEED = 2**31 + 77


def tiny_bench():
    bench = copy.deepcopy(
        workload.load_json(os.path.join(ROOT, "BENCHMARK.json")))
    for name in ("so-tiny", "yago-tiny"):
        bench["configs"].append({
            "name": name, "source": "test size",
            "file": f"benchmarks/stream_bench/testdata/{name}.json",
            "reduced": [], "why": "test size"})
        bench["workloads"].append({
            "name": f"{name}.saturate", "config": name,
            "traffic": "saturate", "chips": 1, "why": "test size"})
    for m in bench["end_to_end"]:
        if "workloads" in m and "so-table2.saturate" in m["workloads"]:
            m["workloads"] += ["so-tiny.saturate", "yago-tiny.saturate"]
    return bench


def drive(workload_name="so-tiny.saturate", seconds=4, overrides=()):
    import jax

    args = run.parse_args(["--workload", workload_name, "--seed", str(SEED),
                           "--seconds", str(seconds), "--trace", "0"]
                          + [f"--override={o}" for o in overrides])
    return run.run(args, devices_check=lambda chips: jax.devices(),
                   bench=tiny_bench())


@pytest.mark.parametrize("cell", ["so-tiny.saturate", "yago-tiny.saturate"])
def test_sound_run_is_correct(cell):
    out = drive(cell)
    assert out["correct"], out["compared"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["metrics"]["ingest_eps"]["value"] > 0
    assert out["diagnostics"]["reference_result_pairs"] > 0
    # the snapshot cadence counts from the window's first batch, and
    # set-up has warmed every program the window runs, snapshots included
    assert out["diagnostics"]["snapshots_in_window"] >= 1
    assert out["diagnostics"]["compiles_in_window"] == 0
    assert out["diagnostics"]["cache_loads_in_window"] == 0
    assert out["diagnostics"]["capacities_at_open"]["n_slots"] == 256


def test_control_lower_precision_fails():
    out = drive(overrides=['backend="mxu_bucket"'])
    assert not out["correct"]
    assert out["compared"]["result_pairs_differing"]["value"] > 0


def test_state_left_unchanged_fails(monkeypatch):
    faults.plant_state_unchanged(monkeypatch.setattr)
    out = drive()
    assert not out["correct"]
    assert out["compared"]["result_pairs_differing"]["value"] > 0


def test_half_batch_left_out_fails(monkeypatch):
    faults.plant_half_batch(monkeypatch.setattr)
    out = drive()
    assert not out["correct"]
    assert out["failed"] > 0


def test_answer_altered_fails(monkeypatch):
    faults.plant_answer_altered(monkeypatch.setattr)
    out = drive()
    assert not out["correct"]
    assert out["compared"]["result_pairs_differing"]["value"] > 0
