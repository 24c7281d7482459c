"""The readers of the program's own spans, on synthetic events with known
answers and on base-fleet and 100m-train windows traced on a TPU v5e
(the first second of a 20 s run, trimmed by ``program_trace.trim``)."""
import math
import os

import pytest

from chip import program_trace as PT
from chip.metrics import (caller_wait_share, idle_input_share,
                          idle_queued_share, queue_wait_us_per_req)

DATA = os.path.join(os.path.dirname(__file__), "data")
FLEET = os.path.join(DATA, "fleet_1s_spans.json.gz")
TRAIN = os.path.join(DATA, "train_1s_spans.json.gz")
DEV, HOST = "/device:TPU:0", "/host:CPU"


def _ev(name, t0, dur, tid=0, plane=HOST, line="python3", **args):
    e = {"plane": plane, "line": line, "tid": tid, "name": name,
         "t0": float(t0), "dur": float(dur)}
    if args:
        e["args"] = args
    return e


def _op(t0, dur):
    return _ev("fusion", t0, dur, plane=DEV, line="XLA Ops")


def _window(t0=1000, dur=10_000):
    return _ev("bench.window", t0, dur, line="main")


def test_queue_wait_per_request_counts_dispatches_in_the_window():
    ev = [_window(),
          _ev("server.dispatch", 500, 10, wait_us=900.0, rows=9,
              oldest_wait_us=200.0),                   # starts before
          _ev("server.dispatch", 2000, 10, wait_us=300.0, rows=2,
              oldest_wait_us=200.0),
          _ev("server.dispatch", 5000, 10, wait_us=500.0, rows=3,
              oldest_wait_us=300.0),
          _ev("server.dispatch", 11_000, 10, wait_us=1.0, rows=1,
              oldest_wait_us=1.0)]                    # starts at the end
    assert PT.queue_wait_us_per_req(ev) == pytest.approx(800.0 / 5)


def test_caller_wait_share_clips_to_the_window():
    ev = [_window(),
          _ev("client.predict_all", 0, 3000, tid=1),   # 2000 inside
          _ev("server.wait", 500, 1000, tid=1),        # 500 inside
          _ev("client.predict_all", 4000, 2000, tid=2),
          _ev("server.wait", 4500, 1000, tid=2)]
    assert PT.caller_wait_share(ev) == pytest.approx(100 * 1500 / 4000)


def test_idle_queued_share_is_idle_while_a_request_waited():
    # device busy 1000..2000 and 6000..7000 of the window 1000..11000:
    # idle 2000..6000 and 7000..11000 (8000 ns)
    ev = [_window(), _op(1000, 1000), _op(6000, 1000),
          # a request waited 3000..5000 (2 us back from its dispatch)
          _ev("server.dispatch", 5000, 100, wait_us=2.5, rows=2,
              oldest_wait_us=2.0),
          # and one 6500..8000, half of it while the device ran
          _ev("server.dispatch", 8000, 100, wait_us=1.5, rows=1,
              oldest_wait_us=1.5),
          # overlapping the first: counted once
          _ev("server.dispatch", 4500, 100, wait_us=1.0, rows=1,
              oldest_wait_us=1.0)]
    assert PT.idle_queued_share(ev) == pytest.approx(100 * 3000 / 8000)


def test_idle_input_share_is_idle_while_the_loop_fed_itself():
    ev = [_window(), _op(1000, 4000), _op(6000, 5000),   # idle 5000..6000
          _ev("train.batch", 4800, 500), _ev("train.place", 5300, 200),
          _ev("train.step", 5500, 5500, rows=64, width=1024,
              tokens=9000)]
    assert PT.idle_input_share(ev) == pytest.approx(100 * 500 / 1000)


def test_idle_by_span_takes_the_innermost_program_span():
    ev = [_window(), _op(1000, 1000),                    # idle 2000..11000
          _ev("client.predict_all", 1000, 10_000, tid=1),
          _ev("server.wait", 1500, 4000, tid=1),          # mid 6500: out
          _ev("client.predict_all", 1000, 10_000, tid=2),
          _ev("server.wait", 5000, 3000, tid=2),          # in at 6500
          _ev("server.wait", 6000, 3000, tid=3)]          # in at 6500
    assert PT.idle_by_span(ev) == [("server.wait", pytest.approx(9e-6))]
    assert PT.idle_by_span([_window(), _op(1000, 1000)]) == [
        ("no program span", pytest.approx(9e-6))]


def test_a_program_without_spans_reads_nothing():
    ev = [_window(), _op(1000, 1000), _ev("bench.predict_all", 2000, 100)]
    for fn in (PT.queue_wait_us_per_req, PT.caller_wait_share,
               PT.idle_queued_share, PT.idle_input_share):
        assert fn(ev) is None


def test_readers_without_a_trace_dir(tmp_path, monkeypatch):
    from chip import harness as H

    class Cell:
        name = "no-such-cell"
    monkeypatch.setattr(H, "TRACE_DIR", tmp_path)
    for mod in (caller_wait_share, idle_input_share, idle_queued_share,
                queue_wait_us_per_req):
        assert mod.read({"cell": Cell()}, {}) is None


def test_trim_keeps_the_window_start(tmp_path):
    ev = [_window(0, 3e9), _op(0.5e9, 1e9),
          _ev("server.wait", 2e9, 10, tid=1, x=1)]
    path = str(tmp_path / "t.json.gz")
    PT.trim(ev, path, seconds=1.0)
    got = PT.load(path)
    assert [e["name"] for e in got] == ["bench.window", "fusion"]
    assert got[0]["dur"] == 1e9


def _finite_share(v):
    return v is not None and math.isfinite(v) and 0.0 <= v <= 100.0


def test_recorded_chip_windows():
    fleet = PT.load(FLEET)
    wait = PT.queue_wait_us_per_req(fleet)
    assert wait is not None and math.isfinite(wait) and wait > 0
    assert _finite_share(PT.caller_wait_share(fleet))
    assert _finite_share(PT.idle_queued_share(fleet))
    assert PT.idle_input_share(fleet) is None      # no train loop there
    idle = dict(PT.idle_by_span(fleet))
    assert sum(idle.values()) > 0.5 and "server.wait" in idle
    train = PT.load(TRAIN)
    assert _finite_share(PT.idle_input_share(train))
    assert PT.queue_wait_us_per_req(train) is None
    assert "train.step" in dict(PT.idle_by_span(train))
