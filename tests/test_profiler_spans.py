"""Layer spans on the profiler's clock: the gateway, the search and the
train loop mark their work in a recorded profile, with the arguments the
benchmark's readers use; the unsampled queue-wait counter agrees with
the spans; the device programs carry stable names; and with the
profiler off no annotation is ever constructed."""
import glob
import os
import threading
import warnings
from collections import Counter

import jax
import numpy as np
import pytest

from repro.configs.costmodel import CostModelConfig
from repro.core import models as CM
from repro.core import tokenizer as TOK
from repro.core import trainer as TR
from repro.core.server import CostModelServer
from repro.core.service import CostModelService
from repro.ir import dataset as DS
from repro.ir import samplers
from repro.opt.search import beam_search

CFG = CostModelConfig(name="span-test", vocab_size=512, max_seq=64,
                      embed_dim=16, conv_channels=(16,) * 3,
                      fc_dims=(32, 16))
GATEWAY = {"client.predict_all", "server.featurize", "server.wait",
           "server.dispatch", "server.collect", "server.resolve"}
TRAIN = {"train.batch", "train.place", "train.step"}
SEARCH_KW = dict(beam_width=2, max_steps=2, eval_budget=12)


@pytest.fixture(scope="module")
def corpus():
    rng = np.random.default_rng(3)
    graphs = [samplers.sample_graph(rng) for _ in range(12)]
    vocab = TOK.fit_vocab([TOK.graph_tokens(g, "ops") for g in graphs],
                          max_size=512)
    return graphs, vocab


def _service(vocab, cfg=CFG):
    params = CM.conv_init(jax.random.PRNGKey(0), cfg,
                          heads=CM.DEFAULT_HEADS)
    stats = {t: {"mu": 0.3, "sigma": 1.7} for t in CM.DEFAULT_HEADS}
    return CostModelService("conv1d", cfg, params, vocab, stats,
                            mode="ops", max_seq=64, max_batch=8)


@pytest.fixture(scope="module")
def train_ds():
    return DS.build_dataset(80, mode="ops", max_seq=64, vocab_size=512,
                            seed=2)


def _gateway_and_train(graphs, vocab, train_ds):
    """Four concurrent callers (a search and plain predict_all calls)
    through one gateway, then two TrainEngine steps; returns the
    gateway's counters before and after."""
    server = CostModelServer(_service(vocab), max_batch=8, flush_us=500)
    server.start(warmup=False)
    try:
        before = server.metrics_snapshot()
        errs = []

        def caller(i):
            try:
                if i == 0:
                    beam_search(server, graphs[0], **SEARCH_KW)
                else:
                    server.predict_all(graphs[i::3])
            except Exception as e:      # surfaced below
                errs.append(e)
        threads = [threading.Thread(target=caller, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads)
        assert not errs, errs
        after = server.metrics_snapshot()
    finally:
        server.stop()
    TR.TrainEngine("conv1d", CFG, CM.DEFAULT_HEADS[0], steps=2,
                   batch_size=16, log_every=10).fit(train_ds)
    return before, after


def _events(trace_dir):
    """Host events of the newest profile under ``trace_dir``: name,
    thread key, start and end (ns), and the event's stats as args."""
    path = sorted(glob.glob(os.path.join(
        str(trace_dir), "plugins", "profile", "*", "*.xplane.pb")))[-1]
    out = []
    with warnings.catch_warnings():     # the stats type has no __module__
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in jax.profiler.ProfileData.from_file(path).planes:
            for tid, line in enumerate(plane.lines):
                for e in line.events:
                    out.append({"name": e.name,
                                "thread": (plane.name, tid),
                                "t0": e.start_ns, "t1": e.end_ns,
                                "args": dict(e.stats)})
    return out


@pytest.fixture(scope="module")
def recorded(corpus, train_ds, tmp_path_factory):
    graphs, vocab = corpus
    trace_dir = tmp_path_factory.mktemp("profile")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        before, after = _gateway_and_train(graphs, vocab, train_ds)
    finally:
        jax.profiler.stop_trace()
    return _events(trace_dir), before, after


def _named(events, name):
    return [e for e in events if e["name"] == name]


def test_every_layer_span_is_recorded(recorded):
    events, _, _ = recorded
    names = {e["name"] for e in events}
    missing = (GATEWAY | TRAIN | {"search.beam"}) - names
    assert not missing
    assert len(_named(events, "train.step")) == 2
    assert len(_named(events, "search.beam")) == 1


def _inside(e, outer):
    return any(o["thread"] == e["thread"] and o["t0"] <= e["t0"]
               and e["t1"] <= o["t1"] for o in outer)


def test_spans_nest_in_their_callers(recorded):
    events, _, _ = recorded
    calls = _named(events, "client.predict_all")
    for name in ("server.featurize", "server.wait"):
        spans = _named(events, name)
        assert spans and all(_inside(e, calls) for e in spans), name
    beams = _named(events, "search.beam")
    assert sum(_inside(c, beams) for c in calls) >= 2   # root + expansion
    for name in ("server.dispatch", "server.collect", "server.resolve"):
        assert not any(_inside(e, calls) for e in _named(events, name))


def test_span_arguments(recorded, corpus):
    events, _, _ = recorded
    graphs, _ = corpus
    n_graphs = Counter(e["args"]["n_graphs"]
                       for e in _named(events, "client.predict_all"))
    assert not Counter(len(graphs[i::3]) for i in (1, 2, 3)) - n_graphs
    assert {e["args"]["hit"] for e in _named(events, "server.featurize")
            } <= {0, 1}
    dispatch = _named(events, "server.dispatch")
    buckets = set(_service(corpus[1]).buckets)
    for e in dispatch:
        a = e["args"]
        assert 1 <= a["rows"] <= a["ladder_rows"] <= 8
        assert a["width"] in buckets
        assert a["path"] in {"full", "deadline", "stagnant", "pipeline"}
        # the oldest request's wait is one term of the batch's sum
        assert 0 < a["oldest_wait_us"] <= a["wait_us"] + 1e-6
        assert a["wait_us"] <= a["rows"] * a["oldest_wait_us"] + 1e-6
    seqs = sorted(e["args"]["batch"] for e in dispatch)
    assert seqs == list(range(1, len(dispatch) + 1))
    assert sorted(e["args"]["batch"]
                  for e in _named(events, "server.collect")) == seqs
    resolve = _named(events, "server.resolve")
    assert sorted(e["args"]["batch"] for e in resolve) == seqs
    assert all(e["args"]["waiters"] >= 1 for e in resolve)
    steps = sorted(_named(events, "train.step"),
                   key=lambda e: e["args"]["step_num"])
    assert [e["args"]["step_num"] for e in steps] == [0, 1]
    for e in steps:
        a = e["args"]
        assert a["rows"] == 16 and a["width"] <= 64
        assert 0 < a["tokens"] <= a["rows"] * a["width"]


def test_queue_wait_counter_matches_spans(recorded):
    events, before, after = recorded
    dispatch = _named(events, "server.dispatch")
    span_wait_s = sum(e["args"]["wait_us"] for e in dispatch) / 1e6
    counted = after["queue_wait_s"] - before["queue_wait_s"]
    assert counted > 0
    assert span_wait_s == pytest.approx(counted, rel=0.01)
    assert after["dispatched"] - before["dispatched"] == sum(
        e["args"]["rows"] for e in dispatch)


def test_profiler_off_constructs_no_annotation(corpus, train_ds,
                                               monkeypatch):
    class Refused(jax.profiler.TraceAnnotation):
        def __init__(self, *a, **k):
            raise AssertionError("annotation built with the profiler off")

    class RefusedStep(jax.profiler.StepTraceAnnotation):
        def __init__(self, *a, **k):
            raise AssertionError("annotation built with the profiler off")
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Refused)
    monkeypatch.setattr(jax.profiler, "StepTraceAnnotation", RefusedStep)
    assert not Refused.is_enabled()
    graphs, vocab = corpus
    before, after = _gateway_and_train(graphs, vocab, train_ds)
    assert after["dispatched"] > before["dispatched"]
    assert after["queue_wait_s"] > before["queue_wait_s"]


@pytest.mark.parametrize("vocab_size", [512, 8192],
                         ids=["baked", "argument"])
def test_serving_forward_is_named(corpus, vocab_size):
    """Both the constants-baked forward and the params-as-argument one
    (over 16 MiB) lower as ``jit_serve_forward``."""
    cfg = CostModelConfig(name="span-test", vocab_size=vocab_size,
                          max_seq=64,
                          embed_dim=16 if vocab_size == 512 else 640,
                          conv_channels=(16,) * 3, fc_dims=(32, 16))
    svc = _service(corpus[1], cfg)
    ids = jax.ShapeDtypeStruct((8, svc.buckets[0]), np.int32)
    text = svc._jit.lower(*svc._jit_args, ids).as_text()
    assert "jit_serve_forward" in text


def test_train_step_span_layout(tmp_path):
    """``train.step`` carries the step's layout: ``chunks`` (1 for a
    whole batch) and ``positions``, the rows times width of each chunk
    summed, as the loader handed the batches out."""
    ds = DS.build_dataset(120, mode="ops", max_seq=128, vocab_size=512,
                          augment_factor=2, seed=1)
    eng = TR.TrainEngine("conv1d", CFG, CM.DEFAULT_HEADS[0], steps=4,
                         batch_size=16, log_every=10)
    y, _ = DS.normalize_targets(ds.targets[CM.DEFAULT_HEADS[0]])
    it = iter(eng.make_loader(ds, y.astype(np.float32)))
    want = []
    for _ in range(4):
        ids = next(it)["ids"]
        parts = getattr(ids, "parts", (ids,))
        want.append((len(parts), sum(p.shape[0] * p.shape[1]
                                     for p in parts), ids.shape))
    assert {c for c, _, _ in want} == {1, 4}
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        eng.fit(ds)
    finally:
        jax.profiler.stop_trace()
    steps = sorted(_named(_events(tmp_path), "train.step"),
                   key=lambda e: e["args"]["step_num"])
    assert len(steps) == 4
    for e, (chunks, positions, shape) in zip(steps, want):
        a = e["args"]
        assert (a["chunks"], a["positions"]) == (chunks, positions)
        assert (a["rows"], a["width"]) == shape
        assert 0 < a["tokens"] <= a["positions"] <= a["rows"] * a["width"]


def _profile(tmp_path, fn):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    return _events(tmp_path)


def test_frontdoor_parse_span(tmp_path):
    """Each ingested text marks one ``frontdoor.parse`` span whose
    arguments are what the parse recovered: its ops, its loops and
    those of unknown trip count, and the lines it lost."""
    from repro.ir import frontdoor as FD

    @jax.jit
    def inner(x):
        return jax.nn.relu(x) * 2.0

    def fn(x, n):
        x = jax.lax.fori_loop(0, 5, lambda i, a: a + 1.0, x)
        x = jax.lax.fori_loop(0, n, lambda i, a: a * 0.5, x)
        return inner(x)
    text = jax.jit(fn).lower(jax.ShapeDtypeStruct((8,), np.float32),
                             jax.ShapeDtypeStruct((), np.int32)).as_text()
    _, st = FD.parse_mlir_stats(text)
    assert (st.whiles, st.unknown_trips, st.lossy) == (2, 1, 0)
    events = _profile(tmp_path, lambda: [FD.ingest(text) for _ in range(2)])
    spans = _named(events, "frontdoor.parse")
    assert len(spans) == 2
    for e in spans:
        assert {k: e["args"][k] for k in
                ("ops", "whiles", "unknown_trips", "lossy")} == {
            "ops": st.ops, "whiles": 2, "unknown_trips": 1, "lossy": 0}


def test_featurize_span_counts_truncation(corpus, tmp_path):
    """``server.featurize`` marks ``truncated`` on a row whose tokens
    were cut at the model's ``max_seq``, and 0 on the others and on
    cache hits."""
    graphs, vocab = corpus
    cfg = CostModelConfig(name="span-test", vocab_size=512, max_seq=32,
                          embed_dim=16, conv_channels=(16,) * 3,
                          fc_dims=(32, 16))
    params = CM.conv_init(jax.random.PRNGKey(0), cfg,
                          heads=CM.DEFAULT_HEADS)
    stats = {t: {"mu": 0.3, "sigma": 1.7} for t in CM.DEFAULT_HEADS}
    svc = CostModelService("conv1d", cfg, params, vocab, stats,
                           mode="ops", max_seq=32, max_batch=8)
    server = CostModelServer(svc, max_batch=8, flush_us=500)
    server.start(warmup=False)
    try:
        events = _profile(tmp_path, lambda: [server.predict_all(graphs)
                                             for _ in range(2)])
    finally:
        server.stop()
    spans = _named(events, "server.featurize")
    fresh = [e["args"]["truncated"] for e in spans if not e["args"]["hit"]]
    hits = [e["args"]["truncated"] for e in spans if e["args"]["hit"]]
    cut = sum(len(TOK.graph_tokens(g, "ops")) > 32 for g in graphs)
    assert 0 < cut < len(graphs)
    assert len(fresh) == len(graphs) and sum(fresh) == cut
    assert len(hits) == len(graphs) and not any(hits)
    assert svc.truncations == cut
