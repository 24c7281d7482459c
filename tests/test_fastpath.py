"""Hot-path featurization + bf16 quantized serving.

Covers the incremental-hashing/encoding overhaul: struct_key caching and
rewrite-threaded hash inheritance, the service ids cache + parent-delta
token splicing, vectorized encode_many, key-first LRU probing (cache
hits never tokenize), the truncation counter, and bf16-vs-f32 drift
gates."""
import jax
import numpy as np
import pytest

from repro.configs.costmodel import CostModelConfig
from repro.core import models as CM
from repro.core import tokenizer as TOK
from repro.core.server import CostModelServer
from repro.core.service import CostModelService
from repro.ir import samplers
from repro.ir.graph import Graph, Tensor
from repro.opt import rewrites as RW


# ---------------------------------------------------------------- fixtures
def _mk_service(**kw):
    cfg = CostModelConfig(name="fastpath", vocab_size=1024, max_seq=160,
                          embed_dim=16, conv_channels=(16,) * 6,
                          fc_dims=(32, 16))
    rng = np.random.default_rng(7)
    graphs = [samplers.sample_graph(rng) for _ in range(24)]
    seqs = [TOK.graph_tokens(g, "ops") for g in graphs]
    seqs += [TOK.graph_tokens(RW.random_rewrite(g, rng), "ops")
             for g in graphs[:8]]
    vocab = TOK.fit_vocab(seqs, max_size=1024)
    params = CM.conv_init(jax.random.PRNGKey(0), cfg, heads=CM.DEFAULT_HEADS)
    stats = {t: {"mu": 0.3, "sigma": 1.7} for t in CM.DEFAULT_HEADS}
    kw = {"mode": "ops", "max_seq": 160, **kw}
    svc = CostModelService("conv1d", cfg, params, vocab, stats, **kw)
    return svc, graphs


@pytest.fixture(scope="module")
def fast_service():
    return _mk_service()


def _scratch_predictions(svc, graphs):
    """Per-graph reference: from-scratch ids, one forward each, and the
    service's denormalize — no LRU probe, ids cache or delta splice."""
    rows = [svc.forward_entries([(g.struct_key_fresh(),
                                  svc._fresh_ids(g)[0])])[0]
            for g in graphs]
    return svc.denormalize_rows(np.stack(rows))


def _rewrite_children(graphs, per_rule=2):
    out = []
    for g in graphs:
        for r in RW.default_rules():
            for s in r.applicable(g)[:per_rule]:
                try:
                    out.append(r.apply(g, s))
                except AssertionError:
                    pass
    return out


# ----------------------------------------------------- incremental hashing
def test_struct_key_cached_and_invalidated():
    t = Tensor((4, 32))
    g = Graph()
    a = g.add_arg(t)
    x = g.add_op("relu", [a], t)
    g.outputs = [x]
    k1 = g.struct_key()
    assert g.struct_key() == k1 == g.struct_key_fresh()
    g.add_op("exp", [x], t)              # append invalidates the cache
    assert g.struct_key() != k1
    g2_key = g.struct_key()
    g.outputs = [g.n_args + 1]           # reassigning outputs too
    assert g.struct_key() != g2_key
    assert g.struct_key() == g.struct_key_fresh()


def test_incremental_equals_scratch_across_all_rules():
    rng = np.random.default_rng(0)
    checked = 0
    for fam in sorted(samplers.SAMPLERS):
        for seed in range(4):
            out = samplers.sample_graph(np.random.default_rng(seed), fam)
            for _ in range(4):
                firing = [(r, s) for r in RW.default_rules()
                          for s in r.applicable(out)]
                if not firing:
                    break
                r, s = firing[int(rng.integers(0, len(firing)))]
                try:
                    out = r.apply(out, s)
                except AssertionError:
                    continue
                assert out.struct_key() == out.struct_key_fresh(), r.name
                checked += 1
    assert checked > 30                  # the loop really exercised rules


def test_incremental_hashing_flag_restores_scratch_walks():
    """A rewrite child inherits its parent's hashes, and its key still
    equals the from-scratch walk that ignores them."""
    g = samplers.sample_graph(np.random.default_rng(3), "bert")
    k = g.struct_key()
    f = RW.REGISTRY["dtype_narrow"]
    child = f.apply(g, f.applicable(g)[0])
    assert child._inherited is not None
    assert child.struct_key() == child.struct_key_fresh()
    assert g.struct_key() == k == g.struct_key_fresh()


def test_rewrite_children_inherit_most_hashes():
    """DCE on an n-op graph re-hashes nothing (all survivors are verbatim
    copies with clean operands); the combine step alone must change."""
    t = Tensor((4, 32))
    g = Graph()
    a = g.add_arg(t)
    live = g.add_op("relu", [a], t)
    g.add_op("exp", [a], t)              # dead
    g.add_op("tanh", [live], t)
    g.outputs = [g.n_args + 2]
    dce = RW.REGISTRY["dce"]
    child = dce.apply(g, dce.applicable(g)[0])
    assert set(child._inherited) == set(range(len(child.values)))
    assert child.struct_key() == child.struct_key_fresh()


# ------------------------------------------------ delta/ids-cache encoding
def test_fast_and_legacy_predictions_identical(fast_service):
    """predict_all (key-first LRU, ids cache, delta splice) equals the
    from-scratch reference on parents, rewrite children and both."""
    fast, graphs = fast_service
    ref, _ = _mk_service()
    children = _rewrite_children(graphs[:10])
    assert children, "rewrites produced no candidates"
    for batch in (graphs, children, graphs + children):
        o1 = fast.predict_all(batch)
        o2 = _scratch_predictions(ref, batch)
        for t in fast.heads:
            np.testing.assert_array_equal(o1[t], o2[t])


def test_delta_splice_equals_fresh_encode(fast_service):
    fast, graphs = fast_service
    fast.predict_all(graphs)             # parents' ids now cached
    children = _rewrite_children(graphs)
    spliced = 0
    for c in children:
        got = fast._delta_ids(c)
        if got is not None:
            fresh_ids, n_tok = fast._fresh_ids(c)
            np.testing.assert_array_equal(got[0], fresh_ids)
            assert got[1] == n_tok
            spliced += 1
    assert spliced > 10                  # the delta path really fired


def test_cache_hit_skips_tokenization(fast_service):
    fast, graphs = fast_service
    g = graphs[0]
    fast.predict_all([g])
    before = fast.phase_stats()["full_encodes"]
    for _ in range(3):                   # repeats: key-first LRU hits
        fast.predict_all([g])
    assert fast.phase_stats()["full_encodes"] == before


def test_server_submit_key_first_parity(fast_service):
    fast, graphs = fast_service
    direct = fast.predict_all(graphs)
    with CostModelServer(fast, max_batch=16, flush_us=500) as server:
        before = fast.phase_stats()["full_encodes"]
        via = server.predict_all(graphs)     # all LRU hits at submit
        assert fast.phase_stats()["full_encodes"] == before
        for t in fast.heads:
            np.testing.assert_array_equal(via[t], direct[t])


# ------------------------------------------------------ truncation counter
@pytest.mark.parametrize("path", ["predict_all", "server_submit"])
def test_truncation_counter_surfaced(path):
    svc, _ = _mk_service(max_seq=32, buckets=(32,))
    rng = np.random.default_rng(1)
    big = None
    while big is None:
        g = samplers.sample_graph(rng, "bert")
        if len(TOK.graph_tokens(g, "ops")) > 32:
            big = g

    def predict():
        if path == "predict_all":
            svc.predict_all([big])
            return
        with CostModelServer(svc, max_batch=4, flush_us=200) as server:
            server.submit(big).result(timeout=120)

    assert svc.truncations == 0
    predict()
    assert svc.truncations == 1
    assert svc.cache_stats()["truncations"] == 1
    predict()                            # LRU hit: no new truncation
    assert svc.cache_stats()["truncations"] == 1


# ------------------------------------------------------------ bf16 serving
def test_bf16_drift_within_gates(fast_service):
    """bf16-quantized serving: params cast once, rows widened to f32
    before the (float32-exact) denormalize; prediction drift vs f32 is
    bounded — Spearman >= 0.99 and small relative error per target."""
    from repro.opt.evaluate import spearman
    fast, graphs = fast_service
    bf16, _ = _mk_service(dtype="bf16")
    p32 = fast.predict_all(graphs)
    pbf = bf16.predict_all(graphs)
    for t in bf16.heads:
        assert pbf[t].dtype == np.float32
        rel = np.abs(pbf[t] - p32[t]) / np.maximum(np.abs(p32[t]), 1e-9)
        assert rel.max() <= 0.05, (t, rel.max())
        assert spearman(pbf[t], p32[t]) >= 0.99, t


def test_bf16_stays_quantized_for_all_families():
    """bf16-cast params must run a bf16 network for every registered
    family — masks/initial state/attention bias follow the embedding
    dtype, so nothing silently promotes back to f32 mid-tower."""
    import jax.numpy as jnp
    cfg = CostModelConfig(name="bf16-kinds", vocab_size=128, max_seq=32,
                          embed_dim=8, conv_filters=(2, 2),
                          conv_channels=(8, 8), fc_dims=(16, 8),
                          lstm_hidden=8)
    ids = np.zeros((2, 32), np.int32)
    ids[:, :6] = 3

    def cast(x):
        a = jnp.asarray(x)
        return a.astype(jnp.bfloat16) \
            if jnp.issubdtype(a.dtype, jnp.floating) else a

    for kind in ("fc", "conv1d", "lstm", "xformer"):
        init_fn, apply_fn, _ = CM.get_model(kind)
        params = init_fn(jax.random.PRNGKey(0), cfg,
                         heads=CM.DEFAULT_HEADS)
        out = apply_fn(jax.tree.map(cast, params), ids)
        for t, v in out.items():
            assert v.dtype == jnp.bfloat16, (kind, t, v.dtype)
            assert np.isfinite(np.asarray(v, np.float32)).all(), (kind, t)


def test_bf16_warmup_covers_programs():
    bf16, _ = _mk_service(dtype="bf16", max_batch=4,
                          buckets=(32, 64), batch_ladder=(1, 4))
    assert bf16.warmup() == 4            # (2 buckets x 2 ladder) programs


def test_bf16_rejects_unknown_dtype():
    with pytest.raises(ValueError):
        _mk_service(dtype="fp8")


# -------------------------------------------------------- tokenizer fixes
def test_tokenize_text_wide_dtype_shapes():
    """Satellite regression: i64/f64/i16/i1 (and bf16) tensor shapes stay
    single tokens instead of shattering into <unk> fragments."""
    text = ("func.func @f(%arg0: tensor<4x4xi64>) { "
            "%0 = stablehlo.add %arg0, %arg0 : 8x8xf64 } "
            "2x3xi16 5xi1 4xbf16 7x2xf16 9xi8 6x6xi32 3x3xf32")
    toks = TOK.tokenize_text(text)
    for want in ("4x4xi64", "8x8xf64", "2x3xi16", "5xi1", "4xbf16",
                 "7x2xf16", "9xi8", "6x6xi32", "3x3xf32"):
        assert want in toks, want
    # no fragment tokens survive from a shattered shape
    assert "5xi" not in toks and "8x8xf6" not in toks


def test_encode_many_matches_encode_loop():
    rng = np.random.default_rng(0)
    seqs = [TOK.graph_tokens(samplers.sample_graph(rng), "ops")
            for _ in range(12)]
    v = TOK.fit_vocab(seqs[:6], max_size=256)   # rest has OOV tokens
    for max_len in (8, 40, 200):
        batch = v.encode_many(seqs, max_len)
        for row, s in zip(batch, seqs):
            np.testing.assert_array_equal(row, v.encode(s, max_len))
    assert v.encode_many([], 16).shape == (0, 16)
    np.testing.assert_array_equal(
        v.encode_many([[]], 16)[0], v.encode([], 16))
