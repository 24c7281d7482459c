"""Substrate tests: checkpointing (atomic/resume/elastic), data pipeline
determinism, optimizer, gradient compression, fault-tolerance supervisor."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import ckpt
from repro.data import pipeline as PIPE
from repro.optim import adamw, compress
from repro.runtime import fault


# ------------------------------------------------------------- checkpoint
def _state():
    return {"w": jnp.arange(12.0).reshape(3, 4),
            "nested": {"b": jnp.ones((5,)), "count": jnp.int32(7)}}


def test_ckpt_save_restore_roundtrip(tmp_path):
    d = str(tmp_path / "ck")
    ckpt.save(d, 10, _state(), extra={"loader": {"epoch": 1}})
    like = jax.tree.map(jnp.zeros_like, _state())
    restored, step, extra = ckpt.restore(d, like, verify=True)
    assert step == 10 and extra["loader"]["epoch"] == 1
    np.testing.assert_allclose(restored["w"], _state()["w"])


def test_ckpt_picks_newest_committed_and_gc(tmp_path):
    d = str(tmp_path / "ck")
    for s in [1, 2, 3, 4, 5]:
        ckpt.save(d, s, _state(), keep=3)
    assert ckpt.latest_steps(d) == [3, 4, 5]
    _, step, _ = ckpt.restore(d, _state())
    assert step == 5


def test_ckpt_ignores_uncommitted(tmp_path):
    d = str(tmp_path / "ck")
    ckpt.save(d, 1, _state())
    # simulate crash mid-save: committed dir without marker
    os.makedirs(os.path.join(d, "step_000000002"))
    _, step, _ = ckpt.restore(d, _state())
    assert step == 1


def test_ckpt_shape_mismatch_raises(tmp_path):
    d = str(tmp_path / "ck")
    ckpt.save(d, 1, _state())
    bad = {"w": jnp.zeros((2, 2)),
           "nested": {"b": jnp.ones((5,)), "count": jnp.int32(0)}}
    with pytest.raises(AssertionError):
        ckpt.restore(d, bad)


def test_ckpt_elastic_reshard_onto_mesh(tmp_path):
    """Restore with explicit shardings (1-device mesh stands in for the
    re-meshed cluster — the code path is identical)."""
    d = str(tmp_path / "ck")
    ckpt.save(d, 3, _state())
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    sh = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    shardings = jax.tree.map(lambda _: sh, _state())
    restored, step, _ = ckpt.restore(d, _state(), shardings=shardings)
    assert restored["w"].sharding == sh


# ------------------------------------------------------------- pipeline
def test_loader_deterministic_and_sharded():
    src = PIPE.ArraySource(x=np.arange(64).reshape(64, 1))
    a = PIPE.Loader(src, 8, seed=3, shard_index=0, num_shards=2)
    b = PIPE.Loader(src, 8, seed=3, shard_index=1, num_shards=2)
    ba = next(iter(a))["x"]
    bb = next(iter(b))["x"]
    assert ba.shape == (4, 1) and bb.shape == (4, 1)
    assert set(ba.ravel()).isdisjoint(set(bb.ravel()))
    # deterministic across re-instantiation
    ba2 = next(iter(PIPE.Loader(src, 8, seed=3, shard_index=0,
                                num_shards=2)))["x"]
    np.testing.assert_array_equal(ba, ba2)


def test_loader_resumes_from_cursor():
    src = PIPE.ArraySource(x=np.arange(64).reshape(64, 1))
    l1 = PIPE.Loader(src, 8, seed=0)
    it = iter(l1)
    first = [next(it)["x"] for _ in range(3)]
    cursor = PIPE.LoaderState(**l1.state.as_dict())
    l2 = PIPE.Loader(src, 8, seed=0, state=cursor)
    fourth = next(iter(l2))["x"]
    it_ref = iter(PIPE.Loader(src, 8, seed=0))
    for _ in range(3):
        next(it_ref)
    np.testing.assert_array_equal(fourth, next(it_ref)["x"])


def test_loader_honors_drop_remainder():
    src = PIPE.ArraySource(x=np.arange(70).reshape(70, 1))
    dropped = PIPE.Loader(src, 16, seed=0, drop_remainder=True)
    kept = PIPE.Loader(src, 16, seed=0, drop_remainder=False)
    assert dropped.steps_per_epoch() == 4
    assert kept.steps_per_epoch() == 5
    it = iter(kept)
    sizes = [len(next(it)["x"]) for _ in range(5)]
    assert sorted(sizes) == [6, 16, 16, 16, 16]
    # sharded: the tail is trimmed to a multiple of num_shards
    sh = PIPE.Loader(src, 16, seed=0, drop_remainder=False, num_shards=4)
    tail = [len(b["x"]) for b, _ in zip(iter(sh), range(5))]
    assert sorted(tail) == [1, 4, 4, 4, 4]   # 6 -> 4 rows, 1 per shard


def test_loader_empty_epoch_raises_not_hangs():
    """batch_size > usable rows must fail loudly on the consumer thread
    (a dead producer would otherwise block q.get() forever)."""
    src = PIPE.ArraySource(x=np.arange(10).reshape(10, 1))
    with pytest.raises(ValueError, match="empty epoch"):
        iter(PIPE.Loader(src, 16, seed=0))


def test_homogeneous_small_buckets_promoted_not_starved():
    """A bucket with fewer rows than the batch size merges into the next
    bucket up instead of being silently excluded every epoch."""
    ids = np.ones((40, 64), np.int32)
    bucket_by = np.where(np.arange(40) < 6, 16, 64)   # 6-row small bucket
    src = PIPE.ArraySource(ids=ids, r=np.arange(40))
    ld = PIPE.Loader(src, 8, seed=0, bucket_by=bucket_by,
                     bucket_mode="homogeneous")
    it = iter(ld)
    seen = set()
    for _ in range(2 * ld.steps_per_epoch()):
        seen.update(next(it)["r"].tolist())
    assert set(range(6)) <= seen, "small-bucket rows never trained"


def test_loader_resume_identical_batch_stream():
    """Same seed + restored cursor => identical batch stream, across an
    epoch boundary, in both plain and bucketed modes."""
    rng = np.random.default_rng(5)
    ids = rng.integers(1, 9, (48, 32)).astype(np.int32)
    bucket_by = np.where(np.arange(48) % 3 == 0, 16, 32)
    for kw in [{}, {"bucket_by": bucket_by},
               {"bucket_by": bucket_by, "bucket_mode": "homogeneous"}]:
        src = PIPE.ArraySource(ids=ids, r=np.arange(48))
        l1 = PIPE.Loader(src, 8, seed=11, **kw)
        it1 = iter(l1)
        for _ in range(8):           # past the 6-step epoch boundary
            next(it1)
        cursor = PIPE.LoaderState(**l1.state.as_dict())
        l2 = PIPE.Loader(src, 8, seed=11, state=cursor, **kw)
        ref = iter(PIPE.Loader(src, 8, seed=11, **kw))
        for _ in range(8):
            next(ref)
        it2 = iter(l2)
        for _ in range(10):
            a, b = next(it2), next(ref)
            np.testing.assert_array_equal(a["ids"], b["ids"])
            np.testing.assert_array_equal(a["r"], b["r"])


def test_synthetic_lm_batches():
    it = PIPE.synthetic_lm_batches(100, 4, 16)
    b = next(it)
    assert b["tokens"].shape == (4, 16)
    np.testing.assert_array_equal(b["tokens"][:, 1:], b["labels"][:, :-1])


# ------------------------------------------------------------- optimizer
def test_adamw_converges_quadratic():
    cfg = adamw.AdamWConfig(lr=0.1, weight_decay=0.0, total_steps=200,
                            warmup_steps=0, schedule="constant")
    params = {"x": jnp.array([5.0, -3.0])}
    state = adamw.init_state(params)
    step = jax.jit(lambda p, s: adamw.apply_updates(
        p, jax.grad(lambda q: jnp.sum(q["x"] ** 2))(p), s, cfg))
    for _ in range(200):
        params, state, _ = step(params, state)
    assert float(jnp.abs(params["x"]).max()) < 1e-2


def test_clip_by_global_norm():
    g = {"a": jnp.ones((4,)) * 100.0}
    clipped, norm = adamw.clip_by_global_norm(g, 1.0)
    assert float(norm) == pytest.approx(200.0)
    assert float(adamw.global_norm(clipped)) == pytest.approx(1.0, rel=1e-5)


def test_schedule_warmup_and_decay():
    cfg = adamw.AdamWConfig(lr=1.0, warmup_steps=10, total_steps=100,
                            schedule="cosine", min_lr_ratio=0.1)
    assert float(adamw.schedule_lr(cfg, jnp.int32(5))) == pytest.approx(0.5)
    assert float(adamw.schedule_lr(cfg, jnp.int32(10))) == pytest.approx(1.0)
    assert float(adamw.schedule_lr(cfg, jnp.int32(100))) == \
        pytest.approx(0.1, rel=1e-3)


# ------------------------------------------------------------- compression
def test_compression_error_feedback_preserves_signal():
    rng = np.random.default_rng(0)
    g = {"w": jnp.asarray(rng.normal(size=(64, 64)), jnp.float32)}
    err = compress.init_error_state(g)
    acc_true = np.zeros((64, 64))
    acc_comp = np.zeros((64, 64))
    for _ in range(50):
        ghat, err = compress.compress_grads(g, err)
        acc_true += np.asarray(g["w"])
        acc_comp += np.asarray(ghat["w"])
    # error feedback: accumulated compressed grads track the true sum
    rel = np.abs(acc_comp - acc_true).max() / np.abs(acc_true).max()
    assert rel < 0.01


def test_quantize_dequantize_bounded_error():
    x = jnp.linspace(-3, 3, 1000)
    q, s = compress.quantize(x)
    back = compress.dequantize(q, s)
    assert float(jnp.abs(back - x).max()) <= float(s) * 0.5 + 1e-6


# ------------------------------------------------------------- fault
def test_supervisor_resumes_after_crash(tmp_path):
    d = str(tmp_path / "ck")
    sup = fault.TrainSupervisor(d, save_every=5, max_step_retries=0)
    calls = {"n": 0}

    def crashing_step(state, step):
        calls["n"] += 1
        if step == 7 and calls["n"] <= 8:
            raise RuntimeError("injected node failure")
        return {"w": state["w"] + 1}

    state = {"w": jnp.zeros(())}
    with pytest.raises(RuntimeError):
        sup.run(state, crashing_step, 10)
    # restart: restore from last committed (step 5) and finish
    state2, start, _ = sup.try_restore({"w": jnp.zeros(())})
    assert start == 7  # crash-save at step 7
    final = sup.run(state2, crashing_step, 10, start_step=start)
    assert float(final["w"]) == 10.0


def test_straggler_detection_and_rebalance():
    mon = fault.HeartbeatMonitor(4, straggler_factor=2.0, timeout_s=10)
    now = 100.0
    for w in range(4):
        for _ in range(5):
            mon.beat(w, step_duration=1.0 if w != 2 else 5.0, now=now)
    assert mon.stragglers(now=now) == [2]
    shards = {0: 4, 1: 4, 2: 4, 3: 4}
    new = mon.rebalance_shards(shards, now=now)
    assert new[2] == 3 and sum(new.values()) == 16
    # timeout-based detection
    mon.beat(3, now=now)
    assert 1 not in mon.stragglers(now=now + 5)
    assert set(mon.stragglers(now=now + 50)) == {0, 1, 2, 3}


# ------------------------------------------------------------ compile cache
def _record_config(monkeypatch):
    import jax
    calls = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: calls.__setitem__(k, v))
    return calls


def test_compile_cache_honours_env_dir(monkeypatch, tmp_path):
    from repro.runtime import compile_cache as CC
    calls = _record_config(monkeypatch)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert CC.enable_compile_cache() == str(tmp_path)
    assert "jax_compilation_cache_dir" not in calls   # JAX reads the env


def test_compile_cache_default_is_fixed_in_checkout(monkeypatch):
    from pathlib import Path
    from repro.runtime import compile_cache as CC
    calls = _record_config(monkeypatch)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = CC.enable_compile_cache()
    assert CC.enable_compile_cache() == first
    assert calls["jax_compilation_cache_dir"] == first
    root = Path(__file__).resolve().parents[1]
    assert Path(first) == root / ".jax_cache"
    ignored = (root / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored
