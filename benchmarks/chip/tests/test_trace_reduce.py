"""The trace reduction on synthetic events and on a trace recorded on
a TPU v5e (a one-second base-fleet window, trimmed)."""
import os

import pytest

from chip import trace_reduce as T

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "fleet_1s_trace.json.gz")


def test_union_clip_and_gaps():
    merged = T.union([(5, 7), (0, 2), (1, 3), (6, 9), (9, 9), (10, 11)])
    assert merged == [(0, 3), (5, 9), (10, 11)]
    assert T.clip(merged, 2, 10) == [(2, 3), (5, 9)]
    assert T.gaps(T.clip(merged, 2, 10), 2, 10) == [(3, 5), (9, 10)]
    assert T.gaps([], 0, 4) == [(0, 4)]


def _ev(plane, line, name, t0, dur, tid=0):
    return {"plane": plane, "line": line, "tid": tid, "name": name,
            "t0": t0, "dur": dur}


def test_reduce_busy_idle_and_labels():
    dev, host = "/device:TPU:0", "/host:CPU"
    ev = [_ev(host, "main", "bench.window", 100, 1000),
          _ev(dev, "XLA Ops", "fusion", 50, 100),       # half inside
          _ev(dev, "XLA Ops", "convolution", 300, 200),
          _ev(dev, "XLA Ops", "fusion", 400, 200),      # overlaps
          _ev(host, "python3", "bench.search", 100, 1000, tid=1),
          _ev(host, "python3", "bench.predict_all", 650, 100, tid=1),
          _ev(host, "python3", "bench.predict_all", 600, 300, tid=2),
          _ev(host, "python3", "bench.search", 100, 1000, tid=3)]
    r = T.reduce(ev)
    assert r["window_s"] == pytest.approx(1e-6)
    assert r["busy_s"] == pytest.approx(350e-9)
    assert r["idle_share"] == pytest.approx(0.65)
    ops = dict(r["device_ops"])
    assert ops["fusion"] == pytest.approx(250e-9)
    gaps = dict(r["idle_gaps"])
    # host threads share a line name and are told apart by index: at
    # the gap 600..1100 (midpoint 850) threads 1 and 3 are in their
    # searches, thread 2 in predict_all, so the search takes it
    assert gaps == pytest.approx({"bench.search": 650e-9})


def test_reduce_needs_window_and_device():
    with pytest.raises(ValueError):
        T.reduce([_ev("/device:TPU:0", "XLA Ops", "x", 0, 1)])
    with pytest.raises(ValueError):
        T.reduce([_ev("/host:CPU", "m", "bench.window", 0, 1)])


@pytest.mark.skipif(not os.path.exists(DATA), reason="no recorded trace")
def test_recorded_chip_trace():
    ev = T.load_events(DATA)
    r = T.reduce(ev)
    assert 0.5 < r["window_s"] < 2.0
    assert 0.0 < r["busy_s"] < r["window_s"]
    assert sum(t for _, t in r["device_ops"]) >= r["busy_s"] * 0.999
    assert sum(t for _, t in r["idle_gaps"]) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-6)
    assert r["n_devices"] == 1
