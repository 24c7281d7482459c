"""Multi-target encoder/head architecture: models, training, serving.

Covers the refactor's contract: every family exposes a shared encode +
per-target heads; joint training is competitive with single-head; the
unified service is cache-consistent, LRU-bounded, and bucket-invariant;
multi-head params roundtrip through the checkpoint layer.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import ckpt
from repro.configs import COSTMODEL_SMALL
from repro.core import models as CM
from repro.core import trainer as TR
from repro.core.service import CostModelService, default_buckets
from repro.ir import dataset as DS, samplers
from repro.runtime.sharding import ShardingRules, tree_shardings

HEADS = CM.DEFAULT_HEADS


@pytest.fixture(scope="module")
def small_dataset():
    return DS.build_dataset(300, mode="ops", max_seq=96, vocab_size=512,
                            augment_factor=2, seed=1)


# ----------------------------------------------------------------- models
def test_multihead_forward_shapes(small_dataset):
    ids = jnp.asarray(small_dataset.ids[:4, :COSTMODEL_SMALL.max_seq])
    for kind in CM.MODELS:
        init_fn, apply_fn, _ = CM.get_model(kind)
        params = init_fn(jax.random.PRNGKey(0), COSTMODEL_SMALL, heads=HEADS)
        assert CM.model_heads(params) == HEADS
        out = apply_fn(params, ids)
        assert set(out) == set(HEADS)
        for t in HEADS:
            assert out[t].shape == (4,)
            assert bool(jnp.isfinite(out[t]).all())


def test_encode_is_shared_across_heads(small_dataset):
    """Heads are linear readouts of the same features: encode() + head
    weights reproduces apply() exactly."""
    ids = jnp.asarray(small_dataset.ids[:4, :COSTMODEL_SMALL.max_seq])
    for kind in CM.MODELS:
        init_fn, apply_fn, _ = CM.get_model(kind)
        params = init_fn(jax.random.PRNGKey(1), COSTMODEL_SMALL, heads=HEADS)
        feats = CM.get_encoder(kind)(params, ids)
        out = apply_fn(params, ids)
        for t in HEADS:
            manual = (feats @ params["heads"][t]["w"]
                      + params["heads"][t]["b"])[..., 0]
            np.testing.assert_allclose(np.asarray(out[t]),
                                       np.asarray(manual), rtol=1e-6)


def test_multihead_axes_match_params():
    """*_axes(heads=...) must stay zip-compatible with the param tree for
    the sharded 100M driver (tree_shardings asserts rank per leaf)."""
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    rules = ShardingRules(mesh)
    for kind in CM.MODELS:
        init_fn, _, axes_fn = CM.get_model(kind)
        for heads in (None, HEADS):
            kw = {"heads": heads} if heads else {}
            params = init_fn(jax.random.PRNGKey(0), COSTMODEL_SMALL, **kw)
            axes = axes_fn(COSTMODEL_SMALL, heads=heads) if heads \
                else axes_fn(COSTMODEL_SMALL)
            shapes = jax.tree.map(lambda x: x.shape, params)
            shardings = tree_shardings(rules, axes, shapes)
            assert jax.tree.structure(params) == \
                jax.tree.structure(shardings)


def test_single_head_path_unchanged(small_dataset):
    """No heads kwarg -> legacy scalar-output layout."""
    ids = jnp.asarray(small_dataset.ids[:4, :COSTMODEL_SMALL.max_seq])
    for kind in CM.MODELS:
        init_fn, apply_fn, _ = CM.get_model(kind)
        params = init_fn(jax.random.PRNGKey(0), COSTMODEL_SMALL)
        assert CM.model_heads(params) is None
        out = apply_fn(params, ids)
        assert out.shape == (4,)


# --------------------------------------------------------------- training
def test_joint_training_comparable_to_single_head(small_dataset):
    """Joint multi-target training reaches per-target accuracy in the same
    ballpark as dedicated single-head models on a small dataset."""
    tr, te = small_dataset.split(0.1)
    # the joint model learns three tasks: give it a larger step budget
    # (still < 3x the single-task budget — the encoder is shared)
    multi = TR.train_model("conv1d", COSTMODEL_SMALL, tr, HEADS,
                           steps=400, batch_size=64, lr=2e-3, seed=0)
    assert multi.heads == HEADS
    assert set(multi.norm_stats) == set(HEADS)
    multi_metrics = TR.evaluate("conv1d", COSTMODEL_SMALL, multi, te)
    for target in HEADS:
        single = TR.train_model("conv1d", COSTMODEL_SMALL, tr, target,
                                steps=220, batch_size=64, lr=2e-3, seed=0)
        sm = TR.evaluate("conv1d", COSTMODEL_SMALL, single, te, target)
        mm = multi_metrics[target]
        # comparable = within 2x normalized RMSE + small absolute slack
        assert mm["rmse_norm"] <= 2.0 * sm["rmse_norm"] + 0.25, \
            (target, mm["rmse_norm"], sm["rmse_norm"])
    # joint loss decreased over training
    losses = [v for _, v in multi.history]
    assert losses[-1] < losses[0]


def test_evaluate_single_target_view_of_multihead(small_dataset):
    tr, te = small_dataset.split(0.1)
    res = TR.train_model("fc", COSTMODEL_SMALL, tr, HEADS,
                         steps=60, batch_size=64)
    per = TR.evaluate("fc", COSTMODEL_SMALL, res, te)
    one = TR.evaluate("fc", COSTMODEL_SMALL, res, te, "latency_us")
    assert one == per["latency_us"]


# ------------------------------------------------------------- checkpoint
def test_multihead_checkpoint_roundtrip(tmp_path):
    params = CM.conv_init(jax.random.PRNGKey(0), COSTMODEL_SMALL,
                          heads=HEADS)
    stats = {t: {"mu": float(i), "sigma": 1.0 + i}
             for i, t in enumerate(HEADS)}
    ckpt.save(str(tmp_path), 7, params,
              extra={"norm_stats": stats, "heads": list(HEADS)})
    like = CM.conv_init(jax.random.PRNGKey(1), COSTMODEL_SMALL, heads=HEADS)
    restored, step, extra = ckpt.restore(str(tmp_path), like, verify=True)
    assert step == 7
    assert extra["norm_stats"] == stats and tuple(extra["heads"]) == HEADS
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(restored)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b))


def test_checkpoint_rejects_head_layout_drift(tmp_path):
    single = CM.conv_init(jax.random.PRNGKey(0), COSTMODEL_SMALL)
    ckpt.save(str(tmp_path), 1, single)
    multi_like = CM.conv_init(jax.random.PRNGKey(0), COSTMODEL_SMALL,
                              heads=HEADS)
    with pytest.raises(ValueError):
        ckpt.restore(str(tmp_path), multi_like)


# ---------------------------------------------------------------- serving
@pytest.fixture(scope="module")
def unified_service(small_dataset):
    tr, _ = small_dataset.split(0.1)
    res = TR.train_model("conv1d", COSTMODEL_SMALL, tr, HEADS,
                         steps=80, batch_size=64)
    return CostModelService(
        "conv1d", COSTMODEL_SMALL, res.params, small_dataset.vocab,
        res.norm_stats, mode="ops", max_seq=96)


def test_service_cached_vs_fresh_identical(unified_service, small_dataset):
    svc = unified_service
    rng = np.random.default_rng(7)
    gs = [samplers.sample_graph(rng) for _ in range(6)]
    first = svc.predict_all(gs)          # fills cache
    second = svc.predict_all(gs)         # served from cache
    fresh = CostModelService(
        "conv1d", COSTMODEL_SMALL, svc.params, small_dataset.vocab,
        svc.norm_stats, mode="ops", max_seq=96)
    uncached = fresh.predict_all(gs)
    for t in HEADS:
        np.testing.assert_array_equal(first[t], second[t])
        np.testing.assert_array_equal(first[t], uncached[t])


def test_lru_eviction_bounds_cache(unified_service, small_dataset):
    svc = CostModelService(
        "conv1d", COSTMODEL_SMALL, unified_service.params,
        small_dataset.vocab, unified_service.norm_stats,
        mode="ops", max_seq=96, cache_size=8)
    rng = np.random.default_rng(8)
    gs = [samplers.sample_graph(rng) for _ in range(30)]
    n_unique = len({svc._fresh_ids(g)[0].tobytes() for g in gs})
    svc.predict_all(gs)
    assert len(svc._cache) == min(8, n_unique)
    svc.predict_all(gs[-4:])             # refresh recency for these four
    keys = set(svc._cache)
    svc.predict_all(gs[-4:])             # pure hits: no eviction, no growth
    assert set(svc._cache) == keys
    assert len(svc._cache) <= 8


def test_bucketed_matches_unbucketed(unified_service, small_dataset):
    """Padding to the bucket instead of max_seq must not change
    predictions — every family masks padding."""
    rng = np.random.default_rng(9)
    gs = [samplers.sample_graph(rng) for _ in range(8)]
    for kind in CM.MODELS:
        init_fn, _, _ = CM.get_model(kind)
        params = init_fn(jax.random.PRNGKey(2), COSTMODEL_SMALL, heads=HEADS)
        stats = {t: {"mu": 0.0, "sigma": 1.0} for t in HEADS}
        # max_seq = cfg.max_seq: the xformer's pos table bounds seq length
        def mk(buckets):
            return CostModelService(
                kind, COSTMODEL_SMALL, params, small_dataset.vocab, stats,
                mode="ops", max_seq=COSTMODEL_SMALL.max_seq,
                buckets=buckets)
        bucketed, unbucketed = mk(None), mk((COSTMODEL_SMALL.max_seq,))
        assert len(bucketed.buckets) > 1
        pb = bucketed.predict_all(gs)
        pu = unbucketed.predict_all(gs)
        for t in HEADS:
            np.testing.assert_allclose(pb[t], pu[t], rtol=1e-5, atol=1e-6,
                                       err_msg=f"{kind}/{t}")


def test_predict_all_empty_batch(unified_service):
    out = unified_service.predict_all([])
    assert set(out) == set(HEADS)
    for v in out.values():
        assert v.shape == (0,)


def test_named_single_head_rejects_mismatched_target(small_dataset):
    """A service that KNOWS it predicts latency must not answer a
    register-pressure request with latency numbers."""
    params = CM.conv_init(jax.random.PRNGKey(0), COSTMODEL_SMALL)
    svc = CostModelService(
        "conv1d", COSTMODEL_SMALL, params, small_dataset.vocab,
        {"mu": 0.0, "sigma": 1.0}, mode="ops", max_seq=96,
        target="latency_us")
    rng = np.random.default_rng(11)
    g = samplers.sample_graph(rng)
    assert svc.predict(g, "latency_us") == svc.predict(g)
    with pytest.raises(KeyError):
        svc.predict(g, "register_pressure")


def test_unroll_advisor_refuses_single_head(small_dataset):
    """A one-head service cannot judge register feasibility: refuse,
    don't silently reuse the latency head."""
    from repro.core.service import UnrollAdvisor
    params = CM.conv_init(jax.random.PRNGKey(0), COSTMODEL_SMALL)
    svc = CostModelService(
        "conv1d", COSTMODEL_SMALL, params, small_dataset.vocab,
        {"mu": 0.0, "sigma": 1.0}, mode="ops", max_seq=96)
    rng = np.random.default_rng(12)
    g = samplers.sample_graph(rng)
    with pytest.raises(ValueError, match="distinct"):
        UnrollAdvisor(svc).advise(g)


def test_kernel_tower_multihead_parity(small_dataset):
    """conv_tower_apply stays a drop-in for conv_apply in both layouts."""
    from repro.kernels import ops as KOPS
    ids = jnp.asarray(small_dataset.ids[:4, :COSTMODEL_SMALL.max_seq])
    params = CM.conv_init(jax.random.PRNGKey(3), COSTMODEL_SMALL,
                          heads=HEADS)
    got = KOPS.conv_tower_apply(params, ids, use_kernel=False)
    want = CM.conv_apply(params, ids)
    assert set(got) == set(HEADS)
    for t in HEADS:
        np.testing.assert_allclose(np.asarray(got[t]), np.asarray(want[t]),
                                   rtol=1e-5, atol=1e-6)


def test_default_buckets_ladder():
    assert default_buckets(256) == (32, 64, 128, 256)
    assert default_buckets(96) == (32, 64, 96)
    assert default_buckets(16) == (16,)
