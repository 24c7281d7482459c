"""TrainEngine: the unified bucketed, fault-tolerant step loop.

Covers the refactor's contract: bucketed (batch_max) training reaches the
same eval metrics as max_seq-padded training on the same seed; the id
storage layout (dense vs bucket-grouped) does not change training at all;
kill-and-resume mid-run reproduces the uninterrupted run's final params
(checkpoint + loader cursor); TrainResult.stats is populated; and the
train_model compatibility wrapper still drives the engine.
"""
import jax
import numpy as np
import pytest

from repro.configs import COSTMODEL_SMALL
from repro.core import trainer as TR
from repro.core.service import pad_slack
from repro.data import pipeline as PIPE
from repro.ir import dataset as DS


@pytest.fixture(scope="module")
def small_dataset():
    return DS.build_dataset(300, mode="ops", max_seq=96, vocab_size=512,
                            augment_factor=2, seed=1)


@pytest.fixture(scope="module")
def split(small_dataset):
    return small_dataset.split(0.1)


def _param_diff(a, b) -> float:
    return max(float(np.abs(np.asarray(x) - np.asarray(y)).max())
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


# -------------------------------------------------------------- bucketing
@pytest.mark.parametrize("kind", ["conv1d", "fc"])
def test_bucketed_training_parity(kind, split):
    """Engine default (batch_max bucketing) must reach eval metrics within
    tolerance of max_seq padding on the same seed — for conv1d (whose
    bucket widths include the pad-slack rule) and a masking family.

    Per-step gradients are width-invariant to ~1e-10 (the same pad-slack
    argument serving relies on); over a few hundred Adam steps that
    amplifies into small param drift, so we compare eval metrics, not
    params."""
    tr, te = split
    res_b = TR.TrainEngine(kind, COSTMODEL_SMALL, "register_pressure",
                           steps=120, batch_size=64, seed=0,
                           bucketed=True).fit(tr)
    res_p = TR.TrainEngine(kind, COSTMODEL_SMALL, "register_pressure",
                           steps=120, batch_size=64, seed=0,
                           bucketed=False).fit(tr)
    mb = TR.evaluate(kind, COSTMODEL_SMALL, res_b, te, "register_pressure")
    mp = TR.evaluate(kind, COSTMODEL_SMALL, res_p, te, "register_pressure")
    assert abs(mb["rmse_norm"] - mp["rmse_norm"]) <= \
        0.10 * mp["rmse_norm"] + 0.02, (mb["rmse_norm"], mp["rmse_norm"])


def test_batch_max_width_contract(split):
    """batch_max mode: identical batch composition to unbucketed loading,
    with each batch's ids at exactly the largest member's bucket (never
    the global max_seq unless a member needs it)."""
    tr, _ = split
    eng = TR.TrainEngine("conv1d", COSTMODEL_SMALL, "register_pressure",
                         batch_size=32, seed=0)
    bucket_by = eng.bucket_assignments(tr)
    assert len(np.unique(bucket_by)) > 1, "corpus has one bucket only"
    y, _ = DS.normalize_targets(tr.targets["register_pressure"])
    loader = eng.make_loader(tr, y.astype(np.float32))
    plain = PIPE.Loader(PIPE.ArraySource(ids=tr.ids, y=y,
                                         row=np.arange(tr.n)), 32, seed=0)
    it, it_ref = iter(loader), iter(plain)
    for _ in range(loader.steps_per_epoch()):
        b, ref = next(it), next(it_ref)
        np.testing.assert_array_equal(b["y"], ref["y"])  # same composition
        want = int(bucket_by[ref["row"]].max())
        assert b["ids"].shape[1] == want, (b["ids"].shape, want)
        np.testing.assert_array_equal(
            b["ids"], ref["ids"][:, :b["ids"].shape[1]])


def test_homogeneous_mode_single_bucket_batches(split):
    tr, _ = split
    slack = pad_slack("conv1d", COSTMODEL_SMALL)
    buckets = DS.default_buckets(tr.max_seq)
    bucket_by = DS.bucket_lengths(tr.get_seq_lens(), buckets, slack)
    src = PIPE.ArraySource(ids=tr.ids, y=np.arange(tr.n, dtype=np.int64))
    ld = PIPE.Loader(src, 32, seed=0, bucket_by=bucket_by,
                     bucket_mode="homogeneous", drop_remainder=False)
    it = iter(ld)
    seen = []
    for _ in range(ld.steps_per_epoch()):
        b = next(it)
        rows = b["y"]
        width = b["ids"].shape[1]
        # one planned bucket per batch; small buckets merge upward, so
        # every member's own bucket fits under the batch width
        assert width in set(bucket_by.tolist())
        assert bucket_by[rows].max() <= width
        seen.extend(rows.tolist())
    assert sorted(seen) == list(range(tr.n))   # full coverage, no dupes


def test_dataset_layout_does_not_change_training(split):
    """Bucket-grouped id storage is an exact drop-in for dense storage."""
    tr, _ = split
    dsb = DS.build_dataset(300, mode="ops", max_seq=96, vocab_size=512,
                           augment_factor=2, seed=1, layout="bucketed")
    trb, _ = dsb.split(0.1)
    np.testing.assert_array_equal(tr.ids, trb.dense_ids())
    a = TR.TrainEngine("conv1d", COSTMODEL_SMALL, "register_pressure",
                       steps=40, batch_size=64, seed=0).fit(tr)
    b = TR.TrainEngine("conv1d", COSTMODEL_SMALL, "register_pressure",
                       steps=40, batch_size=64, seed=0).fit(trb)
    assert _param_diff(a.params, b.params) == 0.0


# ---------------------------------------------------------- fault tolerance
def test_engine_kill_and_resume_reproduces_run(split, tmp_path):
    """Kill mid-run; a fresh engine restores the last committed checkpoint
    (params + optimizer + loader cursor) and must land on the
    uninterrupted run's final params."""
    tr, _ = split
    kw = dict(steps=40, batch_size=32, seed=3)
    full = TR.TrainEngine("conv1d", COSTMODEL_SMALL, "valu_utilization",
                          **kw).fit(tr)

    class Kill(Exception):
        pass

    def killer(step, dt):
        if step == 17:
            raise Kill()

    d = str(tmp_path / "ck")
    with pytest.raises(Kill):
        TR.TrainEngine("conv1d", COSTMODEL_SMALL, "valu_utilization",
                       ckpt_dir=d, save_every=10, **kw).fit(
                           tr, on_step=killer)
    resumed = TR.TrainEngine("conv1d", COSTMODEL_SMALL, "valu_utilization",
                             ckpt_dir=d, save_every=10, **kw).fit(tr)
    assert resumed.stats["steps"] == 30.0   # resumed from step 10
    for a, b in zip(jax.tree.leaves(full.params),
                    jax.tree.leaves(resumed.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)


def test_engine_multihead_with_compression_and_ckpt(split, tmp_path):
    """The full substrate in one run: multi-head joint training, int8
    error-feedback grad compression, checkpointing — through the one
    engine loop."""
    tr, te = split
    heads = ("register_pressure", "latency_us")
    res = TR.TrainEngine("fc", COSTMODEL_SMALL, heads, steps=60,
                         batch_size=64, seed=0, compress_grads=True,
                         ckpt_dir=str(tmp_path / "ck")).fit(tr)
    assert res.heads == heads
    m = TR.evaluate("fc", COSTMODEL_SMALL, res, te)
    assert set(m) == set(heads)
    for t in heads:
        assert np.isfinite(m[t]["rmse_norm"])


# ----------------------------------------------------------------- results
def test_train_result_stats_populated(split):
    tr, _ = split
    res = TR.train_model("fc", COSTMODEL_SMALL, tr, "latency_us",
                         steps=30, batch_size=64, log_every=10)
    for k in ["final_loss", "steps", "wall_time_s", "steps_per_s"]:
        assert k in res.stats, res.stats
    assert res.stats["steps"] == 30.0
    assert res.stats["steps_per_s"] > 0
    assert np.isfinite(res.stats["final_loss"])
    assert res.history and res.history[-1][0] == 30


# ------------------------------------------------------- data parallelism
_DP_SCRIPT = """
import json, sys
import numpy as np
from repro.configs import COSTMODEL_SMALL
from repro.core import models as CM
from repro.core import trainer as TR
from repro.ir import dataset as DS

ds = DS.build_dataset(200, mode="ops", max_seq=64, vocab_size=512,
                      augment_factor=1, seed=3)
out = {}
for md in (4, 1):
    eng = TR.TrainEngine(sys.argv[1], COSTMODEL_SMALL, CM.DEFAULT_HEADS,
                         steps=6, batch_size=16, log_every=1, mesh_data=md)
    res = eng.fit(ds)
    out[md] = [loss for _, loss in res.history]
    if md == 4:
        b = eng.place_batch({"ids": np.zeros((16, 64), np.int32),
                             "y": np.zeros((16, 3), np.float32)})
        out["spec"] = str(b["ids"].sharding.spec)
        out["ids_shards"] = sorted(
            {tuple(s.data.shape) for s in b["ids"].addressable_shards})
        out["y_shards"] = sorted(
            {tuple(s.data.shape) for s in b["y"].addressable_shards})
print(json.dumps(out))
"""


@pytest.mark.parametrize("kind", ["conv1d", "lstm"])
def test_engine_data_parallel_shards_batch_and_matches(kind):
    """mesh_data=4 on 4 virtual CPU devices: the batch is split along
    the mesh's data axis (one quarter per device), and the per-step
    losses match mesh_data=1 on the same seed and batches. Runs in a
    child process: the device count is fixed when JAX starts."""
    import json
    import os
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (src, env.get("PYTHONPATH")) if p)
    proc = subprocess.run([sys.executable, "-c", _DP_SCRIPT, kind],
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["spec"] == "PartitionSpec('data',)"
    assert out["ids_shards"] == [[4, 64]]
    assert out["y_shards"] == [[4, 3]]
    assert len(out["4"]) == len(out["1"]) == 6
    np.testing.assert_allclose(out["4"], out["1"], rtol=1e-5, atol=1e-6)
