"""``100m-train-dp4`` on four virtual CPU devices at a small size: the
run is ``correct`` on the mesh, and neither the control (the reference
in bfloat16) nor a step on a quarter of the batch (the gradient
exchange left out) is. A child process, since the device count is
fixed when JAX starts."""
import json
import os
import subprocess
import sys

import numpy as np

from chip import harness as H
from chip.drivers import train_stream_dp4 as D
from repro.data import pipeline as PIPE

CHILD = r"""
import json, sys
sys.path[:0] = [{bench!r}, {src!r}]
from chip import harness as H
from chip import run as R
from chip.drivers import train_stream_dp4 as D
cell = H.resolve("100m-train-dp4")
cell.config.update({{"vocab_size": 512, "max_seq": 128, "embed_dim": 16,
                    "conv_channels": [16] * 6, "fc_dims": [32, 16]}})
cell.traffic.update({{"programs": 48, "batch_size": 16}})
args = R.parse(["--workload", "100m-train-dp4", "--seed", str(2**31 + 5),
                "--seconds", "2", "--trace", "0"])
quarter = dict([D.FAULTS["quarter_batch"]])
out = [R.execute(args, require_chip=False, cell=cell, t_process=H.now(),
                 hooks=h) for h in ({{}}, {{"control": True}}, quarter)]
print(json.dumps(out))
"""


def test_dp4_correct_on_four_devices_and_control_fails():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = CHILD.format(bench=str(H.BENCH.parent),
                        src=str(H.ROOT / "src"))
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    sound, control, quarter = json.loads(p.stdout.strip().splitlines()[-1])
    assert sound["device"]["count"] == 4
    assert sound["correct"], sound["checks"]
    assert not control["correct"]
    failed = {k for k, c in control["checks"].items()
              if not c["value"] <= c["limit"]}
    assert "residual_gap" in failed
    assert not quarter["correct"], quarter["checks"]


def test_dp4_batches_are_the_same_work_on_every_seed():
    rng = np.random.default_rng(0)
    buckets = rng.choice([128, 256, 512, 1024], size=192)

    def plans(seed):
        loader = PIPE.Loader(PIPE.ArraySource(ids=np.arange(192)), 16,
                             seed=seed, bucket_by=buckets,
                             chunk_multiple=4)
        D.even_batches(loader, batch_seed=20)
        return [loader._epoch_plan(e) for e in range(3)]

    a, b = plans(2**31 + 5), plans(2**31 + 6)
    for pa, pb in zip(a, b):
        rows = np.concatenate([idx for idx, _ in pa])
        assert sorted(rows) == list(range(192))
        key = [(tuple(sorted(idx)), w) for idx, w in pa]
        assert sorted(key) == sorted((tuple(sorted(idx)), w)
                                     for idx, w in pb)
        assert all(w == buckets[idx].max() for idx, w in pa)
    assert any(not np.array_equal(x[0], y[0]) for x, y in zip(a[0], b[0]))
