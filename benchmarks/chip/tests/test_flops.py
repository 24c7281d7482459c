"""flops.py against XLA's own count of the same program, on the CPU at
the base configuration's widths."""
import jax
import numpy as np
import pytest

from chip import flops
from chip import harness as H


@pytest.fixture(scope="module")
def cfg():
    return H.load_json(H.BENCH / "configs" / "costmodel-base.json")


@pytest.mark.parametrize("batch,width", [(1, 32), (16, 128), (64, 256)])
def test_forward_ops_match_xla(cfg, batch, width):
    from repro.core import models as CM
    from chip import serving as SV
    params = SV.weights(cfg, 3)
    ids = np.ones((batch, width), np.int32)
    lowered = jax.jit(lambda i: CM.conv_apply(params, i)).lower(ids)
    xla = lowered.compile().cost_analysis()
    ops, nbytes = flops.forward(cfg, batch, width)
    assert ops == pytest.approx(xla["flops"], rel=0.05)
    assert nbytes <= xla["bytes accessed"]


def test_train_step_counts_three_forward_products(cfg):
    ops, _ = flops.train_step(cfg, 64, 256)
    per_pos = flops.matmul_flops_per_position(cfg)
    assert ops == pytest.approx(3 * 64 * 256 * per_pos, rel=0.01)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        flops.peaks("TPU v99")
    assert flops.peaks("TPU v5 lite")["flops_per_s"] == 197e12
