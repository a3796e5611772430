"""The reader of the engine's result-compaction counters
(``result_overflow_share``) on hand-made recorder state whose numbers are
known: records inside and outside the window, a ring that dropped
records, a program that never counted, and a program without the
recorder."""
import importlib.util
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))

from repro import telemetry  # noqa: E402
from repro.telemetry import CountRecord  # noqa: E402

MS = 1_000_000
T0, T1 = 1_000 * MS, 2_000 * MS          # the window, in ns
REC = {"window": {"t_start": T0 / 1e9, "t_close": T1 / 1e9}}

#: eight fetches and one overflow in the window; one of each before it
COUNTERS = {
    "engine.result_fetches": [CountRecord(T0 - 1, 1)]
    + [CountRecord(T0 + k, 1) for k in range(8)],
    "engine.result_overflows": [CountRecord(T0 - 1, 1),
                                CountRecord(T0 + 3, 1)],
}


def _reader():
    spec = importlib.util.spec_from_file_location(
        "metric_result_overflow_share",
        os.path.join(HERE, "metrics", "result_overflow_share.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _fake_recorder(monkeypatch, state, dropped=()):
    def records(name, since_ns=None, until_ns=None):
        lo = 0 if since_ns is None else since_ns
        hi = float("inf") if until_ns is None else until_ns
        out = [r for r in state.get(name, []) if lo <= r[0] <= hi]
        return out, name in dropped

    def names():
        return [], sorted(state)

    monkeypatch.setattr(telemetry, "records", records)
    monkeypatch.setattr(telemetry, "names", names)


def test_result_overflow_share_reader(monkeypatch):
    reader = _reader()
    _fake_recorder(monkeypatch, COUNTERS)
    assert reader.read(REC) == pytest.approx(1 / 8)

    # a ring that dropped records inside the window gives no reading
    for name in COUNTERS:
        _fake_recorder(monkeypatch, COUNTERS, dropped=(name,))
        assert reader.read(REC) is None, name

    # nor does a program that never counted them (an older checkout)
    _fake_recorder(monkeypatch, {})
    assert reader.read(REC) is None

    # nor one that has no recorder
    import repro

    monkeypatch.delattr(repro, "telemetry")
    monkeypatch.setitem(sys.modules, "repro.telemetry", None)
    assert reader.read(REC) is None
