"""The readers of the engine's bypass and slot-recycling spans
(``bypass_event_ms``, ``slot_recycle_ms``) on hand-made recorder state
whose numbers are known: records inside and outside the window, a ring
that dropped records, a program that never recorded the spans, and a
program without the recorder."""
import importlib.util
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))

from repro import telemetry  # noqa: E402
from repro.telemetry import SpanRecord  # noqa: E402

MS = 1_000_000
T0, T1 = 1_000 * MS, 2_000 * MS          # the window, in ns
REC = {"window": {"t_start": T0 / 1e9, "t_close": T1 / 1e9}}


def _span(start_ms, dur_ms, value=None, parent=None):
    t = T0 + start_ms * MS
    return SpanRecord(t, t + dur_ms * MS, parent, 1, value)


STATE = {
    # three chunks packed nothing (value 0), two reached a dispatch;
    # one lookup and one bypass lie before the window opens
    "engine.intern": [_span(-20, 3, 0), _span(10, 1, 0), _span(20, 2, 1),
                      _span(30, 0.5, 0), _span(40, 4, 1), _span(50, 1, 0)],
    "engine.bypass": [_span(-17, 5, 1), _span(11, 2, 1), _span(31, 1.5, 1),
                      _span(51, 1, 1)],
    "engine.recycle": [_span(-100, 50, 3, "service.expire"),
                       _span(100, 6, 110, "service.expire"),
                       _span(600, 2, 0, "service.expire"),
                       _span(2_001, 9, 4, "service.expire")],
}
EXPECTED = {
    "bypass_event_ms": 7 / 3,   # (1 + 0.5 + 1 + 2 + 1.5 + 1) / 3 bypasses
    "slot_recycle_ms": 4.0,     # (6 + 2) / 2 recyclings
}
#: the spans each reader reads; a ring of any of them that dropped a
#: record in the window gives no reading
READS = {
    "bypass_event_ms": ("engine.intern", "engine.bypass"),
    "slot_recycle_ms": ("engine.recycle",),
}


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        f"metric_{name}", os.path.join(HERE, "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _fake_recorder(monkeypatch, state, dropped=()):
    def records(name, since_ns=None, until_ns=None):
        lo = 0 if since_ns is None else since_ns
        hi = float("inf") if until_ns is None else until_ns
        out = [r for r in state.get(name, []) if lo <= r[0] <= hi]
        return out, name in dropped

    monkeypatch.setattr(telemetry, "records", records)
    monkeypatch.setattr(telemetry, "names",
                        lambda: (sorted(state), []))


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_on_hand_made_records(monkeypatch, name):
    reader = _reader(name)
    _fake_recorder(monkeypatch, STATE)
    assert reader.read(REC) == pytest.approx(EXPECTED[name])

    for span in READS[name]:
        _fake_recorder(monkeypatch, STATE, dropped=(span,))
        assert reader.read(REC) is None, span

    # a program that records none of the reader's spans (an older
    # checkout of this program) leaves the metric out
    _fake_recorder(monkeypatch, {n: v for n, v in STATE.items()
                                 if n not in READS[name]})
    assert reader.read(REC) is None

    # and so does a program that has no recorder at all
    import repro

    monkeypatch.delattr(repro, "telemetry")
    monkeypatch.setitem(sys.modules, "repro.telemetry", None)
    assert reader.read(REC) is None


def test_bypass_reader_ignores_lookups_of_dispatched_chunks(monkeypatch):
    """Only the lookups of chunks that packed nothing belong to a bypass:
    a window of dispatched chunks alone reads nothing."""
    reader = _reader("bypass_event_ms")
    _fake_recorder(monkeypatch, {
        "engine.intern": [_span(10, 5, 1), _span(20, 5, 2)],
        "engine.bypass": []})
    assert reader.read(REC) is None
