"""TrainEngine: the unified bucketed, fault-tolerant step loop.

Covers the refactor's contract: bucketed (batch_max) training reaches the
same eval metrics as max_seq-padded training on the same seed; the id
storage layout (dense vs bucket-grouped) does not change training at all;
kill-and-resume mid-run reproduces the uninterrupted run's final params
(checkpoint + loader cursor); TrainResult.stats is populated; and the
train_model compatibility wrapper still drives the engine.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import COSTMODEL_SMALL
from repro.core import models as CM
from repro.core import trainer as TR
from repro.core.service import pad_slack
from repro.data import pipeline as PIPE
from repro.ir import dataset as DS
from repro.optim import adamw


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
    the global max_seq unless a member needs it). A batch split into
    width-sorted chunks holds the same rows in stable bucket order, each
    chunk at its own largest member's bucket."""
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
    n_split = 0
    for _ in range(loader.steps_per_epoch()):
        b, ref = next(it), next(it_ref)
        want = int(bucket_by[ref["row"]].max())
        assert b["ids"].shape[1] == want, (b["ids"].shape, want)
        plan = PIPE.chunk_plan(bucket_by[ref["row"]])
        if plan is None:
            np.testing.assert_array_equal(b["y"], ref["y"])
            np.testing.assert_array_equal(b["ids"], ref["ids"][:, :want])
            continue
        n_split += 1
        order = plan[0]
        assert np.all(np.diff(bucket_by[ref["row"][order]]) >= 0)
        np.testing.assert_array_equal(b["y"], ref["y"][order])
        rows = ref["row"][order].reshape(PIPE.CHUNKS, -1)
        for part, r in zip(b["ids"].parts, rows):
            w = int(bucket_by[r].max())
            np.testing.assert_array_equal(part, tr.ids[r][:, :w])
    assert n_split, "no batch of the corpus was split"


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
    out = _child(_DP_SCRIPT, kind)
    assert out["spec"] == "PartitionSpec('data',)"
    assert out["ids_shards"] == [[4, 64]]
    assert out["y_shards"] == [[4, 3]]
    assert len(out["4"]) == len(out["1"]) == 6
    np.testing.assert_allclose(out["4"], out["1"], rtol=1e-5, atol=1e-6)


_DP_PLACE_SCRIPT = """
import json, re, sys
import jax
import numpy as np
from repro.configs import COSTMODEL_SMALL
from repro.core import models as CM
from repro.core import trainer as TR
from repro.ir import dataset as DS
from repro.optim import adamw

ds = DS.build_dataset(200, mode="ops", max_seq=64, vocab_size=512,
                      augment_factor=1, seed=3)
out = {}
for md in (4, 1):
    eng = TR.TrainEngine(sys.argv[1], COSTMODEL_SMALL, CM.DEFAULT_HEADS,
                         steps=1, batch_size=16, mesh_data=md)
    res = eng.fit(ds)
    out[md] = [np.asarray(p).ravel().tolist()
               for p in jax.tree.leaves(res.params)]
    if md == 4:
        placed = eng.param_shardings(eng.mesh, res.params)
        out["specs"] = sorted({str(s.spec) for s in
                               jax.tree.leaves(placed)} |
                              {str(p.sharding.spec) for p in
                               jax.tree.leaves(res.params)})
        b = eng.place_batch({"ids": np.ones((16, 32), np.int32),
                             "y": np.zeros((16, 3), np.float32)})
        args = (eng.apply_fn, eng.heads, res.params, b["ids"], b["y"], 0)
        chunk = TR.chunk_grad(*args)
        state = (res.params, adamw.init_state(res.params), None)
        opt = adamw.AdamWConfig(lr=1e-3, total_steps=4, warmup_steps=1)
        for name, fn, a in (
                ("chunk", TR.chunk_grad, args),
                ("apply", TR.apply_grads, (opt, state, (chunk,) * 4))):
            text = fn.lower(*a).compile().as_text()
            out[name] = sum(
                int(np.prod([int(d) for d in dims.split(",") if d]))
                for shapes in re.findall(
                    r"= (.*?) all-reduce(?:-start)?\\(", text)
                for dims in re.findall(r"\\w+\\[([\\d,]*)\\]", shapes))
        out["n_params"] = sum(p.size for p in jax.tree.leaves(res.params))
print(json.dumps(out))
"""


@pytest.mark.parametrize("kind", ["conv1d", "xformer"])
def test_engine_data_parallel_keeps_params_whole(kind):
    """mesh_data=4 on 4 virtual CPU devices: no parameter is split
    along ``data``, as placed or as the step hands it back, and one
    data-parallel step's updated parameters match one device's. The
    xformer puts the ``embed`` axis on most of its weights. A chunk's
    gradient program exchanges nothing; the update sums the chunks'
    partials in one exchange of the whole gradient (and the loss)."""
    out = _child(_DP_PLACE_SCRIPT, kind)
    assert out["specs"] and not any("data" in s for s in out["specs"])
    assert out["chunk"] == 0
    assert out["n_params"] <= out["apply"] <= out["n_params"] + 1
    assert len(out["4"]) == len(out["1"])
    for a, b in zip(out["4"], out["1"]):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


# ------------------------------------------------- width-sorted chunks
FAMILY_CFG = dataclasses.replace(COSTMODEL_SMALL, max_seq=128)
HEADS = ("register_pressure", "valu_utilization", "latency_us")
OPT = adamw.AdamWConfig(lr=1e-3, total_steps=40, warmup_steps=4)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _mixed_batch(kind, n=32, seed=0):
    """One loader batch of ``n`` rows whose lengths span four buckets,
    as the engine's loader hands it out (split when the rule allows)."""
    rng = np.random.default_rng(seed)
    lens = rng.choice([10, 20, 40, 90], size=n)
    ids = np.zeros((n, 128), np.int32)
    for i, length in enumerate(lens):
        ids[i, :length] = rng.integers(1, 512, length)
    y = rng.normal(size=(n, len(HEADS))).astype(np.float32)
    bucket_by = DS.bucket_lengths(lens, DS.default_buckets(128, 16),
                                  pad_slack(kind, FAMILY_CFG))
    ld = PIPE.Loader(PIPE.ArraySource(ids=ids, y=y), n, seed=seed,
                     bucket_by=bucket_by, chunk_multiple=1)
    return next(iter(ld))


def _state(kind):
    params = CM.get_model(kind)[0](jax.random.PRNGKey(0), FAMILY_CFG,
                                   heads=HEADS)
    return (params, adamw.init_state(params), None)


def _dense(chunks):
    w = chunks.shape[1]
    return np.concatenate([PIPE.fit_width(np.asarray(p), w)
                           for p in chunks.parts])


def _assert_steps_match(got, want):
    """Loss, gradient (AdamW's first moment) and updated params equal to
    float32 reassociation."""
    (s1, l1), (s0, l0) = got, want
    np.testing.assert_allclose(float(l1), float(l0), rtol=1e-5)
    m1, m0 = jax.tree.leaves(s1[1]["m"]), jax.tree.leaves(s0[1]["m"])
    scale = max(float(np.abs(np.asarray(m)).max()) for m in m0)
    for a, b in zip(m1, m0):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5 * scale)
    for a, b in zip(jax.tree.leaves(s1[0]), jax.tree.leaves(s0[0])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-7)


@pytest.mark.parametrize("kind", ["conv1d", "lstm", "fc", "xformer"])
def test_split_step_matches_whole_batch(kind):
    """A batch over three or more buckets runs as four width-sorted
    chunks; the step equals the whole batch padded to its widest row."""
    b = _mixed_batch(kind)
    chunks = b["ids"]
    assert isinstance(chunks, PIPE.Chunks)
    assert len({p.shape[1] for p in chunks.parts}) >= 3
    positions = sum(p.shape[0] * p.shape[1] for p in chunks.parts)
    assert positions <= PIPE.SPLIT_GAIN * 32 * chunks.shape[1]
    apply_fn = CM.get_model(kind)[1]
    state, yy = _state(kind), jnp.asarray(b["y"])
    got = TR.make_train_step(apply_fn, OPT, HEADS)(
        state, chunks.map(jnp.asarray), yy)
    want = TR.train_step(apply_fn, OPT, HEADS, state,
                         jnp.asarray(_dense(chunks)), yy)
    _assert_steps_match(got, want)


def _parent_step(apply_fn, opt_cfg, heads):
    """The one-program step as it was before chunks existed."""
    loss_fn = TR.make_loss_fn(apply_fn, heads)

    @jax.jit
    def train_step(state, ids, yy):
        params, opt_state, err = state
        loss, grads = jax.value_and_grad(loss_fn)(params, ids, yy)
        params, opt_state, _ = adamw.apply_updates(
            params, grads, opt_state, opt_cfg)
        return (params, opt_state, err), loss
    return train_step


@pytest.mark.parametrize("mode", ["batch_max", "homogeneous"])
def test_single_width_batch_runs_one_program(mode):
    """A batch of one bucket (any batch in homogeneous mode) stays a
    plain array and gives bit-identical params to the one-program step."""
    rng = np.random.default_rng(1)
    ids = rng.integers(1, 512, (32, 64)).astype(np.int32)
    ids[:, 50:] = 0
    y = rng.normal(size=(32, len(HEADS))).astype(np.float32)
    bucket_by = np.where(np.arange(32) < 16, 64, 32) \
        if mode == "homogeneous" else np.full(32, 64)
    ld = PIPE.Loader(PIPE.ArraySource(ids=ids, y=y), 16, seed=0,
                     bucket_by=bucket_by, bucket_mode=mode,
                     chunk_multiple=1)
    assert ld.chunk_shapes() == [] or mode == "homogeneous"
    b = next(iter(ld))
    assert isinstance(b["ids"], np.ndarray)
    apply_fn = CM.get_model("conv1d")[1]
    state = _state("conv1d")
    args = (state, jnp.asarray(b["ids"]), jnp.asarray(b["y"]))
    got = TR.make_train_step(apply_fn, OPT, HEADS)(*args)
    want = _parent_step(apply_fn, OPT, HEADS)(*args)
    assert float(got[1]) == float(want[1])
    for a, w in zip(jax.tree.leaves(got[0]), jax.tree.leaves(want[0])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(w))


@pytest.mark.parametrize("n", [12, 16])
def test_chunks_leading_slice_is_first_sorted_rows(n):
    """``ids[:n]`` of a split batch (mid-chunk or on a chunk edge) is the
    step over the first n rows in bucket order."""
    b = _mixed_batch("fc")
    chunks = b["ids"]
    head = chunks[:n]
    assert head.shape[0] == n and isinstance(head, PIPE.Chunks)
    np.testing.assert_array_equal(_dense(head),
                                  _dense(chunks)[:n, :head.shape[1]])
    apply_fn = CM.get_model("fc")[1]
    state, yy = _state("fc"), jnp.asarray(b["y"])
    got = TR.make_train_step(apply_fn, OPT, HEADS)(
        state, head.map(jnp.asarray), yy[:n])
    want = TR.train_step(apply_fn, OPT, HEADS, state,
                         jnp.asarray(_dense(head)), yy[:n])
    _assert_steps_match(got, want)
    with pytest.raises(TypeError):
        chunks[4:8]


def test_chunk_programs_compile_before_the_first_step(split):
    """Every chunk program is compiled before step 1: over two epochs the
    steps compile nothing but whole-batch programs (batches the loader
    left whole), and the chunk programs' caches do not grow."""
    from jax import monitoring
    tr, _ = split
    eng = TR.TrainEngine("lstm", COSTMODEL_SMALL, HEADS, steps=1,
                         batch_size=16, min_bucket=16, seed=2)
    y, _ = DS.stacked_normalized_targets(tr.targets, HEADS)
    steps = 2 * eng.make_loader(tr, y).steps_per_epoch()
    eng = TR.TrainEngine("lstm", COSTMODEL_SMALL, HEADS, steps=steps,
                         batch_size=16, min_bucket=16, seed=2)
    seen = {"on": False, "compiles": 0, "split": 0}
    sizes = {}

    def on_compile(event, duration, **_):
        if seen["on"] and event == COMPILE_EVENT:
            seen["compiles"] += 1
    monitoring.register_event_duration_secs_listener(on_compile)
    compile_chunks = eng.compile_chunks

    def compiled_then_count(loader, *a):
        compile_chunks(loader, *a)
        assert loader.chunk_shapes()
        for fn in (TR.chunk_grad, TR.apply_grads, TR.train_step):
            sizes[fn] = fn._cache_size()
        seen["on"] = True
    eng.compile_chunks = compiled_then_count
    place = eng.place_batch

    def counted_place(batch):
        seen["split"] += isinstance(batch.get("ids"), PIPE.Chunks)
        return place(batch)
    eng.place_batch = counted_place
    try:
        res = eng.fit(tr)
    finally:
        seen["on"] = False
        monitoring.unregister_event_duration_listener(on_compile)
    assert res.stats["steps"] == steps and seen["split"] >= steps // 2
    assert TR.chunk_grad._cache_size() == sizes[TR.chunk_grad]
    assert TR.apply_grads._cache_size() == sizes[TR.apply_grads]
    assert seen["compiles"] == \
        TR.train_step._cache_size() - sizes[TR.train_step]


_DP_SPLIT_SCRIPT = """
import json
import numpy as np
from repro.configs import COSTMODEL_SMALL
from repro.core import models as CM
from repro.core import trainer as TR
from repro.data import pipeline as PIPE
from repro.ir import dataset as DS

ds = DS.build_dataset(120, mode="ops", max_seq=128, vocab_size=512,
                      augment_factor=2, seed=1)
out = {}
for md in (2, 1):
    eng = TR.TrainEngine("conv1d", COSTMODEL_SMALL, CM.DEFAULT_HEADS,
                         steps=6, batch_size=16, log_every=1, mesh_data=md)
    y, _ = DS.stacked_normalized_targets(ds.targets, eng.heads)
    it = iter(eng.make_loader(ds, y))
    out["split_" + str(md)] = sum(
        isinstance(next(it)["ids"], PIPE.Chunks) for _ in range(6))
    res = eng.fit(ds)
    out[md] = [loss for _, loss in res.history]
print(json.dumps(out))
"""


def _child(script, *args, devices=4):
    """Run ``script`` in a child process with ``devices`` virtual CPU
    devices (the device count is fixed when JAX starts); its last stdout
    line, as JSON."""
    import json
    import os
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (src, env.get("PYTHONPATH")) if p)
    proc = subprocess.run([sys.executable, "-c", script, *args],
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_split_step_data_parallel_matches_one_device():
    """mesh_data=2: chunks of four rows split two a device, and the
    losses of split steps match one device's."""
    out = _child(_DP_SPLIT_SCRIPT, devices=2)
    assert out["split_2"] == out["split_1"] >= 3
    assert len(out["2"]) == len(out["1"]) == 6
    np.testing.assert_allclose(out["2"], out["1"], rtol=1e-5, atol=1e-6)
