"""Replicated serving tier: spawned replicas behind the struct-key
router — prediction parity vs the single-process service, routing that
keeps per-replica LRUs hot, the shared cross-replica cache tier, the
wire format, and the client's retry/backoff/health/shed state machine
(driven through a fake transport, no processes needed)."""
import hashlib
import os
import queue
import signal
import threading
import time

import jax
import numpy as np
import pytest

from repro.configs.costmodel import CostModelConfig
from repro.core import models as CM
from repro.core import tokenizer as TOK
from repro.core.server import ServerOverloadedError
from repro.core.service import CostModelService
from repro.ir import samplers
from repro.serving import (HashRing, ReplicaClient, ServiceSpec,
                           SharedRowCache, start_replicas)
from repro.serving import transport as T

CFG = CostModelConfig(name="repl-test", vocab_size=512, max_seq=64,
                      embed_dim=16, conv_channels=(16,) * 2,
                      fc_dims=(32,))
N_REPLICAS = 4


def _sha_keys(n, salt=""):
    """Production-shaped keys: struct_key() is sha1 hex, so its high
    bits are uniform — the ring's hex fast path hashes those, and keys
    like f"{i:040x}" (all-zero prefixes) would degenerate onto one
    replica by construction."""
    return [hashlib.sha1(f"{salt}k{i}".encode()).hexdigest()
            for i in range(n)]


@pytest.fixture(scope="module")
def corpus():
    rng = np.random.default_rng(11)
    graphs = [samplers.sample_graph(rng) for _ in range(24)]
    vocab = TOK.fit_vocab([TOK.graph_tokens(g, "ops") for g in graphs],
                          max_size=512)
    return graphs, vocab


@pytest.fixture(scope="module")
def service(corpus):
    _, vocab = corpus
    params = CM.conv_init(jax.random.PRNGKey(3), CFG,
                          heads=CM.DEFAULT_HEADS)
    stats = {t: {"mu": 0.2, "sigma": 1.3} for t in CM.DEFAULT_HEADS}
    return CostModelService("conv1d", CFG, params, vocab, stats,
                            mode="ops", max_seq=64, max_batch=8,
                            buckets=(32, 64), batch_ladder=(1, 2, 4, 8))


@pytest.fixture(scope="module")
def spec(service):
    return ServiceSpec.from_service(service)


@pytest.fixture(scope="module")
def tier(spec):
    """One real spawned tier shared by the process-backed tests."""
    tier = start_replicas(spec, N_REPLICAS, n_clients=3,
                          flush_us=300.0, start_timeout_s=240.0)
    yield tier
    tier.stop()


# ------------------------------------------------------------ real tier
def test_replicated_parity_matches_direct(corpus, service, tier):
    """Predictions through 4 replicas + router == single-process
    predict_all, within float tolerance (acceptance criterion)."""
    graphs, _ = corpus
    want = service.predict_all(graphs)
    client = ReplicaClient(tier.client_handle(0))
    got = client.predict_all(graphs)
    assert set(got) == set(want)
    for t in want:
        np.testing.assert_allclose(got[t], want[t], rtol=1e-6)
    # repeat from the client's local LRU: same answers
    again = client.predict_all(graphs)
    for t in want:
        np.testing.assert_allclose(again[t], want[t], rtol=1e-6)
    assert client.shed_count == 0


def test_replicated_predict_text_parity(service, tier):
    """The real-MLIR front door through the replica tier: same
    predictions as the single-process service for the same text, and
    garbage degrades to a structured IngestError, never an exception
    (acceptance criterion: tier parity for predict_text)."""
    from repro.ir import frontdoor as FD
    text = FD.AFFINE_EXAMPLE
    want = service.predict_text(text)
    assert not isinstance(want, FD.IngestError)
    client = ReplicaClient(tier.client_handle(1))
    got = client.predict_text(text)
    assert not isinstance(got, FD.IngestError)
    assert got.key == want.key
    for t, v in want.predictions.items():
        np.testing.assert_allclose(got.predictions[t], v, rtol=1e-6)
    # repeat answers from the client-side LRU, identically
    again = client.predict_text(text)
    np.testing.assert_allclose(
        [again.predictions[t] for t in sorted(want.predictions)],
        [want.predictions[t] for t in sorted(want.predictions)],
        rtol=1e-6)
    err = client.predict_text(b"\x00\xff")
    assert isinstance(err, FD.IngestError)
    # a real lowered arch subgraph rides the same path (truncated to
    # this fixture's 64-token bucket identically on both sides)
    from repro.ir import stablehlo as SH
    _, _, mlir = SH.lower_arch_corpus(["qwen3-0.6b"], seq=4)[0]
    direct = service.predict_text(mlir)
    via = client.predict_text(mlir)
    assert not isinstance(via, FD.IngestError)
    assert via.key == direct.key
    for t, v in direct.predictions.items():
        np.testing.assert_allclose(via.predictions[t], v, rtol=1e-6)


def test_replicated_use_kernel_parity(corpus, service):
    """use_kernel survives the ServiceSpec export/import round trip and
    a spawned replica tier serving the fused Pallas forward returns the
    same predictions as the plain-jnp single-process service (allclose
    — the kernel's accumulation order differs from XLA's)."""
    graphs, vocab = corpus
    ksvc = CostModelService("conv1d", CFG, service.params, vocab,
                            service.norm_stats, mode="ops", max_seq=64,
                            max_batch=8, buckets=(32, 64),
                            batch_ladder=(1, 2, 4, 8), use_kernel=True)
    want = service.predict_all(graphs)
    kspec = ServiceSpec.from_service(ksvc)
    assert kspec.use_kernel is True
    rebuilt = kspec.build()
    assert rebuilt.use_kernel is True
    got = rebuilt.predict_all(graphs)
    assert set(got) == set(want)
    for t in want:
        np.testing.assert_allclose(got[t], want[t], rtol=2e-4, atol=2e-4)
    ktier = start_replicas(kspec, 2, n_clients=1, flush_us=300.0,
                           start_timeout_s=240.0)
    try:
        client = ReplicaClient(ktier.client_handle(0))
        via_tier = client.predict_all(graphs)
        for t in want:
            np.testing.assert_allclose(via_tier[t], want[t],
                                       rtol=2e-4, atol=2e-4)
        assert client.shed_count == 0
    finally:
        ktier.stop()


def test_struct_key_routing_preserves_replica_lru(corpus, tier):
    """Struct-key routing sends a key to the same replica every time,
    so repeat queries hit that replica's own LRU (acceptance
    criterion). The client runs with local_cache=False so every query
    actually travels to the replicas."""
    graphs, _ = corpus
    client = ReplicaClient(tier.client_handle(1), local_cache=False)
    client.clear_caches()              # fresh replica LRUs for this test
    tier.shared_cache.clear()
    # cache COUNTERS are cumulative across the module-scoped tier, so
    # judge this test's traffic by before/after deltas
    before = {s["replica_id"]: s["cache"]
              for s in client.replica_stats() if s}
    client.predict_all(graphs)         # pass 1: compulsory misses
    client.predict_all(graphs)         # pass 2: must be replica-LRU hits
    stats = [s for s in client.replica_stats() if s]
    assert len(stats) == N_REPLICAS
    delta = {}
    for s in stats:
        b, c = before[s["replica_id"]], s["cache"]
        delta[s["replica_id"]] = (c["hits"] - b["hits"],
                                  c["misses"] - b["misses"])
    used = {r: d for r, d in delta.items() if d[0] + d[1] > 0}
    assert len(used) >= 2, "routing degenerated onto one replica"
    for r, (hits, misses) in used.items():
        # each unique key: exactly one miss (pass 1) then hits — a
        # routing flap would send pass-2 keys to a cold replica and
        # drag the hit share under 0.5
        assert hits / (hits + misses) >= 0.5 - 1e-9, \
            f"replica {r} LRU went cold: hits={hits} misses={misses}"
    total_misses = sum(d[1] for d in used.values())
    uniq = len({g.struct_key() for g in graphs})
    assert total_misses == uniq


def test_shared_cache_serves_cross_replica_misses(corpus, service, tier):
    """A row published to the shared tier is served without a forward
    pass: plant a sentinel row for a never-seen graph and check the
    tier answers with it."""
    rng = np.random.default_rng(99)
    g = samplers.sample_graph(rng, "unet")
    key = service.key_of(g)
    client = ReplicaClient(tier.client_handle(2), local_cache=False)
    client.clear_caches()
    sentinel = np.full((len(service.heads),), 0.125, np.float32)
    tier.shared_cache.put(key, sentinel)
    got = client.predict_all([g])
    want = service.denormalize_rows(sentinel[None])
    for t in want:
        np.testing.assert_allclose(got[t], want[t], rtol=1e-6)


def test_replicated_entrypoint_exports(tier):
    assert tier.n_replicas == N_REPLICAS
    assert all(tier.alive())


# ------------------------------------------------- shared cache (unit)
def test_shared_row_cache_roundtrip():
    c = SharedRowCache(n_heads=3, n_slots=64)
    assert c.get("a" * 40) is None
    row = np.array([1.5, -2.0, 0.25], np.float32)
    c.put("a" * 40, row)
    np.testing.assert_array_equal(c.get("a" * 40), row)
    assert c.fill() == 1
    # refresh in place, not a second slot
    c.put("a" * 40, row * 2)
    np.testing.assert_array_equal(c.get("a" * 40), row * 2)
    assert c.fill() == 1
    # non-hex keys digest through sha1
    c.put("not-a-hex-key", row)
    np.testing.assert_array_equal(c.get("not-a-hex-key"), row)
    c.clear()
    assert c.fill() == 0
    assert c.get("a" * 40) is None


def test_shared_row_cache_eviction_bounded():
    c = SharedRowCache(n_heads=2, n_slots=8)
    keys = [f"{i:040x}" for i in range(64)]
    c.put_many([(k, np.array([i, -i], np.float32))
                for i, k in enumerate(keys)])
    assert c.fill() <= 8                # capacity is a hard bound
    live = [k for k in keys if c.get(k) is not None]
    assert live                         # some survivors...
    for k in live:                      # ...with intact rows
        i = int(k, 16)
        np.testing.assert_array_equal(
            c.get(k), np.array([i, -i], np.float32))


def test_shared_row_cache_get_many():
    c = SharedRowCache(n_heads=1, n_slots=32)
    c.put("b" * 40, np.array([7.0], np.float32))
    got = c.get_many(["b" * 40, "c" * 40])
    np.testing.assert_array_equal(got[0], [7.0])
    assert got[1] is None


# ------------------------------------------------------ transport (unit)
def test_pack_unpack_entries_roundtrip():
    entries = [("k1", np.arange(8, dtype=np.int32)),
               ("k2", np.arange(100, 116, dtype=np.int32)),
               ("k3", np.zeros(0, np.int32))]
    keys, lens_b, ids_b = T.pack_entries(entries)
    back = T.unpack_entries(keys, lens_b, ids_b)
    assert [k for k, _ in back] == ["k1", "k2", "k3"]
    for (_, a), (_, b) in zip(entries, back):
        np.testing.assert_array_equal(a, b)
    assert T.pack_entries([]) == ([], b"", b"")


def test_pack_unpack_rows_roundtrip():
    rows = [np.array([1.0, 2.0, 3.0], np.float32),
            np.array([-1.0, 0.5, 9.0], np.float32)]
    rows_b, nh = T.pack_rows(rows)
    assert nh == 3
    np.testing.assert_array_equal(T.unpack_rows(rows_b, nh),
                                  np.stack(rows))


def test_service_spec_rebuild_parity(corpus, service, spec):
    """build() in the SAME process must reproduce the service exactly —
    the cross-process parity case is test_replicated_parity."""
    graphs, _ = corpus
    rebuilt = spec.build()
    want = service.predict_all(graphs)
    got = rebuilt.predict_all(graphs)
    for t in want:
        np.testing.assert_allclose(got[t], want[t], rtol=1e-6)


def test_export_import_cache_roundtrip(corpus, service):
    graphs, _ = corpus
    donor = ServiceSpec.from_service(service).build()
    donor.predict_all(graphs)
    items = donor.export_cache()
    assert len(items) == len({g.struct_key() for g in graphs})
    recip = ServiceSpec.from_service(service).build()
    assert recip.import_cache(items) == len(items)
    before = recip.phase_stats()["forward_s"]
    got = recip.predict_all(graphs)    # all answered from imported rows
    assert recip.phase_stats()["forward_s"] == before
    want = service.predict_all(graphs)
    for t in want:
        np.testing.assert_allclose(got[t], want[t], rtol=1e-6)


# ------------------------------------------------------------- hash ring
def test_hash_ring_stable_and_balanced():
    ring = HashRing(4, vnodes=32)
    keys = _sha_keys(1000)
    owners = [ring.primary(k) for k in keys]
    assert owners == [ring.primary(k) for k in keys]   # deterministic
    counts = np.bincount(owners, minlength=4)
    assert (counts > 0).all()
    assert counts.max() <= 3 * counts.min() + 8        # no degenerate split
    order = ring.route(keys[0])
    assert sorted(order) == [0, 1, 2, 3]               # full fallback chain
    assert ring.route(keys[0], 2) == order[:2]


# --------------------------------------- router state machine (no procs)
def _row_for(key: str, n_heads: int) -> np.ndarray:
    h = int(key[:8], 16) if len(key) == 40 else abs(hash(key))
    return (np.arange(n_heads, dtype=np.float32) + h % 97) / 97.0


class FakeTransport:
    """Scripted tier: behavior(replica, keys) decides each request's
    fate — ("ok",), ("overload", retry_after), ("err",), ("drop",)."""

    def __init__(self, n_replicas, behavior, n_heads=3):
        self.n_replicas = n_replicas
        self.client_id = 0
        self.behavior = behavior
        self.n_heads = n_heads
        self.q = queue.Queue()
        self.sent = []                 # (replica, keys) per request

    def send(self, replica, msg):
        if msg[0] != T.MSG_REQ:
            return                     # control traffic: ignored here
        _, _client, bid, keys, _lens, _ids = msg
        self.sent.append((replica, list(keys)))
        act = self.behavior(replica, keys)
        if act[0] == "ok":
            rows_b, nh = T.pack_rows(
                [_row_for(k, self.n_heads) for k in keys])
            self.q.put((T.MSG_RES, bid, list(range(len(keys))),
                        rows_b, nh))
        elif act[0] == "overload":
            self.q.put((T.MSG_OVERLOAD, bid, list(range(len(keys))),
                        act[1]))
        elif act[0] == "err":
            self.q.put((T.MSG_ERR, bid, list(range(len(keys))),
                        "scripted failure"))
        # "drop": no reply at all (dead replica)

    def recv(self, timeout):
        return self.q.get(timeout=timeout)


@pytest.fixture()
def fake_client(spec):
    def make(behavior, **kw):
        tr = FakeTransport(4, behavior)
        kw.setdefault("backoff_s", 0.001)
        kw.setdefault("timeout_s", 0.25)
        kw.setdefault("cooldown_s", 0.02)
        return ReplicaClient(transport=tr, spec=spec, **kw), tr
    return make


def _entries(n, start=0):
    return [(k, np.arange(4, dtype=np.int32))
            for k in _sha_keys(n, salt=f"{start}-")]


def test_router_happy_path_routes_by_ring(fake_client):
    client, tr = fake_client(lambda r, ks: ("ok",))
    ents = _entries(32)
    got = client._fetch(ents)
    assert set(got) == {k for k, _ in ents}
    for k in got:
        np.testing.assert_array_equal(got[k], _row_for(k, 3))
    for replica, keys in tr.sent:      # every key on its ring primary
        for k in keys:
            assert client.ring.primary(k) == replica
    assert sum(h.ok for h in client.health) == len(tr.sent)


def test_router_reroutes_around_overloaded_replica(fake_client):
    client, tr = fake_client(
        lambda r, ks: ("overload", 0.01) if r == 0 else ("ok",))
    ents = _entries(64)
    primaries = {k: HashRing(4).primary(k) for k, _ in ents}
    assert any(p == 0 for p in primaries.values())
    got = client._fetch(ents)
    assert len(got) == len(ents)       # everything resolved via fallback
    assert client.health[0].overload >= 1
    assert client.health[0].consecutive_failures >= 1
    assert client.health[0].unhealthy_until > time.monotonic() - 1.0
    assert client.shed_count == 0
    # retried keys landed on a non-0 replica the second time
    retried = [(r, ks) for r, ks in tr.sent[1:] if r != 0
               and any(primaries[k] == 0 for k in ks)]
    assert retried


def test_router_sheds_when_all_replicas_overloaded(fake_client):
    client, tr = fake_client(lambda r, ks: ("overload", 0.001),
                             max_retries=2)
    with pytest.raises(ServerOverloadedError):
        client._fetch(_entries(4))
    assert client.shed_count == 1
    # 3 rounds (initial + 2 retries), each round >= 1 request
    rounds = len(tr.sent)
    assert rounds >= 3
    assert sum(h.overload for h in client.health) == rounds


def test_router_honors_retry_after_hint(fake_client):
    state = {"n": 0}

    def behavior(r, ks):
        state["n"] += 1
        return ("overload", 0.15) if state["n"] == 1 else ("ok",)

    client, _ = fake_client(behavior)
    ents = _entries(1)
    t0 = time.monotonic()
    got = client._fetch(ents)
    assert len(got) == 1
    assert time.monotonic() - t0 >= 0.15   # backoff floored by the hint


def test_router_reroutes_around_dead_replica(fake_client):
    ents = _entries(1, start=5000)
    dead = HashRing(4).primary(ents[0][0])
    client, tr = fake_client(
        lambda r, ks: ("drop",) if r == dead else ("ok",),
        timeout_s=0.05)
    got = client._fetch(ents)
    assert len(got) == 1
    assert client.health[dead].timeout >= 1
    assert tr.sent[0][0] == dead           # tried the primary first
    assert tr.sent[-1][0] != dead          # resolved on a fallback


def test_router_shared_client_concurrent_fetch(fake_client):
    """One ReplicaClient shared by many threads (the serve driver's
    closed-loop session): replies for different in-flight batches
    arrive on ONE queue, so the reply demux must hand each thread its
    own batch instead of dropping what it didn't send. Pre-demux this
    shed spuriously under concurrency."""
    client, tr = fake_client(lambda r, ks: ("ok",), timeout_s=5.0)
    n_threads, per_thread = 8, 12
    errs, done = [], []

    def worker(w):
        try:
            for i in range(per_thread):
                ents = _entries(3, start=w * 1000 + i * 10)
                got = client._fetch(ents)
                assert set(got) == {k for k, _ in ents}
                for k in got:
                    np.testing.assert_array_equal(got[k], _row_for(k, 3))
            done.append(w)
        except Exception as e:           # pragma: no cover - regression
            errs.append(e)

    threads = [threading.Thread(target=worker, args=(w,))
               for w in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    assert len(done) == n_threads
    assert client.shed_count == 0
    assert sum(h.timeout for h in client.health) == 0
    assert not client._mail and not client._live   # demux drained


def test_router_scripted_error_counts_and_reroutes(fake_client):
    ents = _entries(1, start=900)
    bad = HashRing(4).primary(ents[0][0])
    client, _ = fake_client(
        lambda r, ks: ("err",) if r == bad else ("ok",))
    got = client._fetch(ents)
    assert len(got) == 1
    assert client.health[bad].err == 1
    st = client.stats()
    assert st["health"][bad]["err"] == 1
    assert st["shed_count"] == 0


# --------------------------------------------- hard failure (real tier)
def test_replica_sigkill_mid_load_recovers(corpus, service, spec):
    """SIGKILL a replica while a client is driving load: in-flight and
    subsequent requests reroute to the survivor (zero exceptions, zero
    wrong predictions), the supervisor respawns the dead slot, and ring
    ownership lands back on the respawned replica."""
    from repro.serving import ReplicaSupervisor
    graphs, _ = corpus
    want = service.predict_all(graphs)
    tier2 = start_replicas(spec, 2, n_clients=1, flush_us=300.0,
                           start_timeout_s=240.0)
    sup = None
    try:
        # death detection is exitcode-driven; the huge heartbeat
        # timeout keeps wedge detection out of this test's way
        sup = ReplicaSupervisor(tier2, heartbeat_s=0.25,
                                heartbeat_timeout_s=60.0,
                                restart_backoff_s=0.05,
                                start_timeout_s=240.0).start()
        client = ReplicaClient(tier2.client_handle(0),
                               local_cache=False, timeout_s=2.0,
                               cooldown_s=0.05)
        results, errs = [], []
        stop = threading.Event()

        def load():
            while not stop.is_set():
                try:
                    results.append(client.predict_all(graphs))
                except Exception as e:    # pragma: no cover - regression
                    errs.append(e)
                    return

        t = threading.Thread(target=load)
        t.start()
        time.sleep(0.5)                    # mid-load
        os.kill(tier2.procs[0].pid, signal.SIGKILL)
        deadline = time.monotonic() + 240.0
        while time.monotonic() < deadline:
            st = sup.stats()
            if st["restarts_recovered"] >= 1 and not st["respawning"]:
                break
            time.sleep(0.25)
        stop.set()
        t.join(timeout=120.0)
        assert not t.is_alive()
        assert not errs                    # rerouting absorbed the death
        assert results
        for r in results:                  # zero wrong predictions
            for tgt in want:
                np.testing.assert_allclose(r[tgt], want[tgt], rtol=1e-6)
        # the client actually saw (and rode out) the failure
        assert client.health[0].timeout + client.health[0].reroutes >= 1
        st = sup.stats()
        assert st["restarts_total"] >= 1
        assert st["restarts_recovered"] >= 1
        assert any(rec["replica"] == 0 and rec["reason"] == "died"
                   for rec in st["restart_log"])
        assert all(tier2.alive())
        # ownership restored: slot 0 serves its keys again once its
        # routing cooldown (escalated during the outage, possibly
        # refreshed by the load thread's final timeout) drains
        before = client.health[0].ok
        deadline = time.monotonic() + 30.0
        while client.health[0].ok == before and \
                time.monotonic() < deadline:
            time.sleep(0.5)
            client.predict_all(graphs)
        assert client.health[0].ok > before
        payloads = [p for p in client.replica_stats() if p]
        assert {p["replica_id"] for p in payloads} == {0, 1}
    finally:
        if sup is not None:
            sup.stop()
        tier2.stop()


def test_start_replicas_refuses_accelerator_backend(monkeypatch):
    """On a non-CPU backend the tier refuses before spawning anything:
    a chip belongs to one process, and this process would hold it."""
    import multiprocessing

    def no_spawn(*a, **k):
        raise AssertionError("start_replicas reached process spawning")

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(multiprocessing, "get_context", no_spawn)
    with pytest.raises(RuntimeError, match="one process at a time") as e:
        start_replicas(None, 2)
    assert "'tpu'" in str(e.value) and "ROADMAP" in str(e.value)
