"""The reader of ``step_pad_share``, on synthetic ``train.step`` spans
with known answers, and on a program whose spans predate ``positions``."""
import os

import pytest

from chip.metrics import step_pad_share as SPS

HOST = "/host:CPU"
TRAIN = os.path.join(os.path.dirname(__file__), "data",
                     "train_1s_spans.json.gz")


def _ev(name, t0, dur, **args):
    e = {"plane": HOST, "line": "python3", "tid": 0, "name": name,
         "t0": float(t0), "dur": float(dur)}
    if args:
        e["args"] = args
    return e


def _window():
    return _ev("bench.window", 1000, 10_000)


def test_counts_steps_that_start_in_the_window():
    ev = [_window(),
          _ev("train.step", 500, 100, tokens=1, positions=100),  # before
          _ev("train.step", 2000, 100, step_num=4, rows=64, width=1024,
              tokens=300, positions=1000, chunks=4),
          _ev("train.step", 5000, 100, step_num=5, rows=64, width=512,
              tokens=200, positions=1000, chunks=1),
          _ev("train.step", 11_000, 100, tokens=1, positions=100)]  # after
    assert SPS.step_pad_share(ev) == pytest.approx(75.0)


def test_spans_without_positions_give_no_reading():
    ev = [_window(),
          _ev("train.step", 2000, 100, step_num=4, rows=64, width=1024,
              tokens=300)]
    assert SPS.step_pad_share(ev) is None
    assert SPS.step_pad_share([_window()]) is None


def test_no_trace_gives_no_reading():
    class Cell:
        name = "no-such-cell"
    assert SPS.read({"cell": Cell()}, {}) is None


def test_recorded_window_predates_positions():
    from chip import program_trace as PT
    assert SPS.step_pad_share(PT.load(TRAIN)) is None
