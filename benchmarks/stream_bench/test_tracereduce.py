"""The trace reduction, on a hand-built trace whose numbers are known and
on a short trace of ``so-table2.saturate`` recorded on a TPU v5e
(``testdata/so-table2.saturate.xplane.pb.gz``: 4.2 s of window, 32
frontier ingest dispatches)."""
import gzip
import os
import sys
from types import SimpleNamespace as NS

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import tracereduce  # noqa: E402

NS_ = 1e-9
RECORDED = os.path.join(HERE, "testdata", "so-table2.saturate.xplane.pb.gz")


def _line(name, events):
    return NS(name=name, events=[NS(name=n, start_ns=s, duration_ns=d)
                                 for n, s, d in events])


def _planes():
    host = NS(name="/host:CPU", lines=[_line("python", [
        ("bench.window", 1000, 9000),          # window 1000..10000
        ("bench.service_ingest", 1000, 4000),  # 1000..5000
        ("bench.decode", 3500, 1000),          # 3500..4500
        ("bench.wait_due", 6000, 3000),        # 6000..9000
    ])])
    device = NS(name="/device:TPU:0", lines=[
        _line("XLA Modules", [("jit__ingest_frontier(1)", 1500, 1800),
                              ("jit__expire(2)", 5200, 600),
                              ("jit__ingest_frontier(1)", 500, 700)]),
        _line("XLA Ops", [("%while.5 = (f32[8]) while(...)", 1500, 1800),
                          ("%fusion.1 = f32[8] fusion(...)", 1500, 1000),
                          ("%scatter.2 = f32[8] scatter(...)", 2600, 700),
                          ("%fusion.1 = f32[8] fusion(...)", 5200, 600),
                          ("%copy.3 = f32[8] copy(...)", 500, 700)]),
    ])
    return [host, device]


def test_reduce_hand_built_trace():
    out = tracereduce.reduce_planes(_planes(), ["_ingest_frontier"])
    assert out["window_s"] == pytest.approx(9000 * NS_)
    # busy: 1000..1200 (the copy, clipped), 1500..3300, 5200..5800
    assert out["busy_s"] == pytest.approx((200 + 1800 + 600) * NS_)
    prog = out["programs"]["_ingest_frontier"]
    assert prog["count"] == 1          # the 500..1200 run starts outside
    assert prog["device_s"] == pytest.approx(1800 * NS_)
    ops = dict(out["device_ops"])
    assert ops["_ingest_frontier/fusion.1"] == pytest.approx(1000 * NS_)
    assert ops["_ingest_frontier/scatter.2"] == pytest.approx(700 * NS_)
    assert ops["_ingest_frontier/while.5"] == pytest.approx(100 * NS_)
    assert ops["_expire/fusion.1"] == pytest.approx(600 * NS_)
    gaps = dict(out["idle_gaps"])
    # idle: 1200..1500, 3300..5200, 5800..10000
    assert gaps["service_ingest"] == pytest.approx((300 + 200 + 500) * NS_)
    assert gaps["decode"] == pytest.approx(1000 * NS_)
    assert gaps["untracked"] == pytest.approx((200 + 200 + 1000) * NS_)
    assert gaps["wait_due"] == pytest.approx(3000 * NS_)
    assert sum(gaps.values()) == pytest.approx(out["window_s"]
                                               - out["busy_s"])


def test_no_window_span_is_an_error():
    planes = _planes()
    planes[0].lines[0].events.pop(0)
    with pytest.raises(ValueError):
        tracereduce.reduce_planes(planes)


@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData

    with gzip.open(RECORDED, "rb") as f:
        data = ProfileData.from_serialized_xspace(f.read())
    return tracereduce.reduce_planes(
        data.planes, ["_ingest_frontier", "_delete_frontier"])


def test_recorded_trace_window_and_busy(recorded):
    assert recorded["devices"] == 1
    assert recorded["window_s"] == pytest.approx(4.246486924)
    assert recorded["busy_s"] == pytest.approx(3.052957261)
    assert 0 < recorded["busy_s"] < recorded["window_s"]


def test_recorded_trace_programs(recorded):
    ingest = recorded["programs"]["_ingest_frontier"]
    # one module execution per host dispatch span (32 in the window)
    assert ingest["count"] == 32
    assert ingest["device_s"] == pytest.approx(3.045787155)
    assert recorded["programs"]["_delete_frontier"]["count"] == 0
    assert ingest["device_s"] <= recorded["busy_s"]


def test_recorded_trace_breakdown(recorded):
    ops = recorded["device_ops"]
    assert len(ops) == 10
    assert all(name.startswith("_ingest_frontier/") for name, _t in ops)
    assert ops[0][1] >= ops[-1][1] > 0
    gaps = dict(recorded["idle_gaps"])
    assert max(gaps, key=gaps.get) == "decode"
    assert sum(gaps.values()) == pytest.approx(
        recorded["window_s"] - recorded["busy_s"])
