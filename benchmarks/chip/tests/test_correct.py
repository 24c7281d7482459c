"""``correct`` through a whole run on the CPU at small sizes: true for
the program as it is, false with a fault planted under the timed path,
and false for the control: the reference in bfloat16, put in the
program's place.

The harness's look for a chip is skipped; everything after it runs as
on the chip, with the limits of the cells' traffic files."""
import pytest

from chip import faults as F
from chip import harness as H
from chip import run as R

TINY_100M = {"vocab_size": 512, "max_seq": 128, "embed_dim": 16,
             "conv_channels": [16] * 6, "fc_dims": [32, 16]}
SMALL = {
    "base-fleet": ({}, {"programs": 80, "workers": 4, "warm_searches": 4,
                        "check_rows": 300}),
    "100m-train": (TINY_100M, {"programs": 48, "batch_size": 16}),
}


def _run(name, hooks=None, seed=2**31 + 3):
    cell = H.resolve(name)
    cfg, tr = SMALL[name]
    cell.config.update(cfg)
    cell.traffic.update(tr)
    args = R.parse(["--workload", name, "--seed", str(seed),
                    "--seconds", "2", "--trace", "0"])
    return R.execute(args, require_chip=False, cell=cell,
                     t_process=H.now(), hooks=hooks or {})


def _failed(res):
    """Names of the compared numbers over their limits."""
    return {k for k, c in res["checks"].items() if not c["value"] <= c["limit"]}


@pytest.mark.parametrize("name", ["base-fleet"])
def test_serving_answer_altered(name):
    assert _run(name)["correct"]
    res = _run(name, F.hooks("alter_answers"))
    assert not res["correct"] and _failed(res)


@pytest.mark.parametrize("name", ["base-fleet"])
def test_serving_control_fails(name):
    # row_gap_mean is the number the control fails on the chip
    res = _run(name, {"control": True})
    assert not res["correct"] and "row_gap_mean" in _failed(res)


def test_train_correct_and_control():
    assert _run("100m-train")["correct"]
    # residual_gap is the number the control fails on the chip
    res = _run("100m-train", {"control": True})
    assert not res["correct"] and "residual_gap" in _failed(res)


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_train_faults(fault):
    res = _run("100m-train", F.hooks(fault))
    assert not res["correct"]
