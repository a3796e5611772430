"""The readers of the program's spans and counters (``programspans.py``
and the five metrics that use it), on synthetic recorder state whose
numbers are known: records inside and outside the window, a ring that
dropped records, and a program without the recorder."""
import importlib.util
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))

from repro import telemetry  # noqa: E402
from repro.telemetry import CountRecord, SpanRecord  # noqa: E402

MS = 1_000_000
T0, T1 = 1_000 * MS, 2_000 * MS          # the window, in ns
REC = {"window": {"t_start": T0 / 1e9, "t_close": T1 / 1e9}}


def _span(start_ms, dur_ms, parent=None):
    t = T0 + start_ms * MS
    return SpanRecord(t, t + dur_ms * MS, parent, 1, None)


STATE = {
    "engine.result_copy": [_span(-5, 4), _span(10, 2), _span(20, 4),
                           _span(1_001, 9)],
    "engine.decode_scan": [_span(30, 1), _span(40, 2)],
    "executor.dispatch": [_span(k * 100, 90) for k in range(4)],
    "executor.sync.flush_counts": [_span(5, 1)],
    "executor.sync.drain_dist": [_span(105, 3), _span(-50, 40)],
    "checkpoint.capture": [_span(200, 10), _span(500, 20)],
    "checkpoint.join": [_span(201, 5, "checkpoint.capture"),
                        _span(900, 30)],
    "frontier.slab_rows": [CountRecord(T0 - 1, 10_000),
                           CountRecord(T0 + 5, 640), CountRecord(T1, 640)],
    "frontier.rows_relaxed": [CountRecord(T0 - 1, 999),
                              CountRecord(T0 + 5, 32), CountRecord(T1, 96)],
}
EXPECTED = {
    "result_copy_ms": 3.0,                  # (2 + 4) / 2
    "decode_scan_ms": 1.5,
    "host_sync_ms": 1.0,                    # (1 + 3) / 4 dispatches
    "snapshot_stall_ms": 30.0,              # (10 + 20 + 30) / 2 captures
    "frontier_slab_occupancy": 0.1,         # (32 + 96) / (640 + 640)
}


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        f"metric_{name}", os.path.join(HERE, "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _fake_recorder(monkeypatch, dropped=()):
    def records(name, since_ns=None, until_ns=None):
        lo = 0 if since_ns is None else since_ns
        hi = float("inf") if until_ns is None else until_ns
        out = [r for r in STATE.get(name, []) if lo <= r[0] <= hi]
        return out, name in dropped

    def names():
        return (sorted(n for n, v in STATE.items()
                       if isinstance(v[0], SpanRecord)),
                sorted(n for n, v in STATE.items()
                       if isinstance(v[0], CountRecord)))

    monkeypatch.setattr(telemetry, "records", records)
    monkeypatch.setattr(telemetry, "names", names)


def test_readers_on_synthetic_recorder_state(monkeypatch):
    readers = {name: _reader(name) for name in EXPECTED}
    _fake_recorder(monkeypatch)
    for name, want in EXPECTED.items():
        assert readers[name].read(REC) == pytest.approx(want), name

    # a ring that dropped records inside the window gives no reading
    _fake_recorder(monkeypatch, dropped=("engine.result_copy",
                                         "engine.decode_scan",
                                         "executor.sync.drain_dist",
                                         "checkpoint.join",
                                         "frontier.rows_relaxed"))
    for name in EXPECTED:
        assert readers[name].read(REC) is None, name

    # nor does a program that has no recorder (an older checkout)
    import repro

    monkeypatch.delattr(repro, "telemetry")
    monkeypatch.setitem(sys.modules, "repro.telemetry", None)
    for name in EXPECTED:
        assert readers[name].read(REC) is None, name
