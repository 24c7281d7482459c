"""Benchmark harness — one entry per paper table/figure + system benches.

Prints ``name,us_per_call,derived`` CSV rows (per the repo convention).

  paper_rmse        — §4: RMSE of FC / LSTM / Conv1D on register pressure
                      and vALU utilization (ops-only tokens).
  operand_ablation  — Fig 6: ops-only vs ops+operands Conv1D accuracy,
                      %-exact for register pressure.
  inference_speed   — §5 claim: Conv1D model is much faster than LSTM.
  kernel_bench      — fused Pallas serving forward (ids-in conv kernel,
                      lstm recurrence kernel) vs the plain-XLA apply
                      over the serving (bucket x ladder) shape set:
                      f32/bf16 parity, wall time, and cost_analysis
                      bytes fed through launch/roofline.py (roofline
                      fraction + aggregate HBM-traffic reduction; gated
                      by gate.py::gate_kernel_bench).
  serve_bench       — unified multi-target service vs three single-target
                      services on the same request stream (req/s).
  serve_concurrent  — async micro-batching CostModelServer under 1/8/64
                      closed-loop clients vs serialized per-request
                      predict_all (req/s + latency percentiles).
  obs_overhead      — unified-telemetry tax on the gateway hot path:
                      steady req/s with tracing + registry export +
                      drift sentinel on vs off (gated >= 0.97x), plus
                      a forced-sampling span-tree completeness check
                      (gated >= 0.99) and drift-gauge presence.
  opt_search        — repro.opt beam search over rewrite sequences
                      through the server vs the one-shot FusionAdvisor
                      baseline (graphs/s + oracle latency improvement).
  search_fleet      — N concurrent beam_search workers against ONE
                      CostModelServer gateway: candidates-costed/s with
                      the incremental hashing + encode_many hot path vs
                      the from-scratch baseline (flag-switched), plus
                      cross-search cache hit rates, batch occupancy,
                      per-phase timing split, and bf16-vs-f32 drift.
  roofline_table    — reads experiments/dryrun/*.json into the §Roofline
                      table (derived = roofline fraction).
  chaos_serve       — supervised 2-replica tier under a seeded scripted
                      fault plan (kill, wedge, drop/delay/dup, torn
                      shared-cache slot): availability, recovery time,
                      bit-parity of non-degraded replies, and obs
                      counter presence (gated by gate_chaos_serve).

``--full`` uses paper-scale settings (20k+ graphs); default is CI-scale.
``--json-dir DIR`` additionally writes one ``BENCH_<name>.json`` record
per bench (the CI bench-smoke job uploads these as artifacts, and
``benchmarks/gate.py`` enforces the serve_concurrent perf gate on them).
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.costmodel import CostModelConfig
from repro.core import models as CM
from repro.core import trainer as TR
from repro.ir import dataset as DS
from repro.runtime.compile_cache import enable_compile_cache


def _row(name, us, derived):
    print(f"{name},{us:.2f},{derived}", flush=True)


def _bench(fn, *args, iters=20, warmup=3):
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / iters * 1e6


# ---------------------------------------------------------------- paper_rmse
def paper_rmse(full: bool = False, seed: int = 0):
    n = 10000 if full else 2000
    steps = {"fc": 3000 if full else 500,
             "lstm": 1200 if full else 200,
             "conv1d": 3000 if full else 700}
    cfg = CostModelConfig(name="bench", vocab_size=4096, max_seq=160,
                          embed_dim=64, conv_channels=(64,) * 6,
                          fc_dims=(256, 64), lstm_hidden=64)
    ds = DS.build_dataset(n, mode="ops", max_seq=160, vocab_size=4096,
                          augment_factor=2, seed=seed)
    tr, te = ds.split(0.1)
    results = {}
    for target in ["register_pressure", "valu_utilization"]:
        for kind in ["fc", "lstm", "conv1d"]:
            t0 = time.time()
            res = TR.TrainEngine(kind, cfg, target, steps=steps[kind],
                                 batch_size=128, lr=2e-3, seed=seed).fit(tr)
            m = TR.evaluate(kind, cfg, res, te, target)
            results[(kind, target)] = m
            _row(f"paper_rmse/{kind}/{target}", (time.time() - t0) * 1e6,
                 f"rmse_rel={m['rmse_rel_pct']:.2f}%"
                 f";mape={m['mape_pct']:.2f}%"
                 f";exact={m['exact_pct']:.1f}%")
    return results


# ---------------------------------------------------------- operand_ablation
def operand_ablation(full: bool = False, seed: int = 0):
    n = 6000 if full else 2000
    steps = 1800 if full else 700
    out = {}
    for mode, fs in [("ops", (2, 2, 2, 2, 2, 2)),
                     ("ops_operands", (16, 16, 8, 8, 2, 1))]:
        max_seq = 160 if mode == "ops" else 640  # ~4x longer sequences
        cfg = CostModelConfig(
            name=f"bench-{mode}", vocab_size=8192, max_seq=max_seq,
            embed_dim=64, conv_filters=fs, conv_channels=(64,) * 6,
            fc_dims=(256, 64))
        ds = DS.build_dataset(n, mode=mode, max_seq=max_seq,
                              vocab_size=8192, augment_factor=2, seed=seed)
        tr, te = ds.split(0.1)
        t0 = time.time()
        res = TR.TrainEngine("conv1d", cfg, "register_pressure",
                             steps=steps, batch_size=64, lr=2e-3,
                             seed=seed).fit(tr)
        m = TR.evaluate("conv1d", cfg, res, te, "register_pressure")
        out[mode] = m
        _row(f"operand_ablation/{mode}", (time.time() - t0) * 1e6,
             f"rmse_rel={m['rmse_rel_pct']:.2f}%;exact={m['exact_pct']:.1f}%"
             f";within5={m['within5_pct']:.1f}%")
    return out


# ---------------------------------------------------------- inference_speed
def inference_speed(full: bool = False, seed: int = 0):
    cfg = CostModelConfig(name="bench", vocab_size=4096, max_seq=256,
                          embed_dim=64, conv_channels=(64,) * 6,
                          fc_dims=(256, 64), lstm_hidden=64)
    rng = np.random.default_rng(seed)
    ids = jnp.asarray(rng.integers(1, 4096, (64, 256)), jnp.int32)
    out = {}
    for kind in ["fc", "conv1d", "lstm"]:
        init_fn, apply_fn, _ = CM.get_model(kind)
        params = init_fn(jax.random.PRNGKey(seed), cfg)
        f = jax.jit(apply_fn)
        us = _bench(f, params, ids)
        out[kind] = us
        _row(f"inference_speed/{kind}", us, f"per_graph_us={us/64:.2f}")
    _row("inference_speed/conv_vs_lstm", 0.0,
         f"speedup={out['lstm']/out['conv1d']:.1f}x")
    return out


# ------------------------------------------------------------- kernel_bench
def _ragged_ids(rng, B, S, vocab):
    """Random token ids with ragged valid lengths (PAD id 0), the
    serving distribution: short graphs bucket-padded up to S."""
    ids = rng.integers(1, vocab, (B, S))
    lens = rng.integers(max(1, S // 4), S + 1, (B,))
    ids[np.arange(S)[None, :] >= lens[:, None]] = 0
    return jnp.asarray(ids, jnp.int32)


def _cost_bytes_flops(fn, *args):
    """(bytes accessed, flops) of ``fn`` from the compiled module's
    cost_analysis."""
    ca = jax.jit(fn).lower(*args).compile().cost_analysis()
    return float(ca.get("bytes accessed", 0.0)), float(ca.get("flops", 0.0))


def kernel_bench(full: bool = False, seed: int = 0):
    """Fused Pallas serving forward vs the plain-XLA apply, over the
    serving (bucket x batch-ladder) shape set.

    Per shape: f32 parity (max abs err vs conv_apply/lstm_apply), wall
    time, and modeled HBM traffic — unfused bytes from the compiled
    reference's ``cost_analysis()``, fused bytes from
    ``fused_forward_bytes`` (ids, the XLA gather's embedding rows and
    ``(B, S, E)`` activations written and read back, the other params,
    out) — both fed through ``launch/roofline.py`` for roofline
    fractions. bf16 parity pools
    predictions across every shape and reports per-head Spearman vs the
    f32 reference. ``gate.py::gate_kernel_bench`` enforces f32 parity,
    bf16 Spearman >= 0.99, and an aggregate >= 3x traffic reduction
    always; the fused-vs-ref wall-clock ratio only on non-interpret
    backends (interpret-mode wall time measures the Pallas emulator,
    not the kernel)."""
    from repro.kernels import ops as KOPS
    from repro.launch.roofline import RooflineReport
    from repro.opt.evaluate import spearman

    cfg = CostModelConfig(name="bench", vocab_size=4096, max_seq=256,
                          embed_dim=64, conv_channels=(64,) * 6,
                          fc_dims=(256, 64), lstm_hidden=64)
    buckets = (64, 128, 256)
    ladder = (4, 8, 32, 64) if full else (8, 32)
    interpret = jax.default_backend() == "cpu"
    iters, warmup = (3, 1) if interpret else (20, 3)
    rng = np.random.default_rng(seed)
    heads = CM.DEFAULT_HEADS

    def _cast16(p):
        return jax.tree.map(
            lambda a: a.astype(jnp.bfloat16)
            if jnp.issubdtype(a.dtype, jnp.floating) else a, p)

    out = {"backend": jax.default_backend(), "interpret": interpret,
           "buckets": list(buckets), "batch_ladder": list(ladder),
           "shapes": [], "models": {}}

    for kind, init, apply_fn, fused_fn in (
            ("conv1d", CM.conv_init, CM.conv_apply,
             KOPS.conv_forward_apply),
            ("lstm", CM.lstm_init, CM.lstm_apply,
             KOPS.lstm_forward_apply)):
        p32 = init(jax.random.PRNGKey(seed), cfg, heads=heads)
        p16 = _cast16(p32)
        ref_jit = jax.jit(apply_fn)
        max_err = 0.0
        ref_us_total = fused_us_total = 0.0
        unfused_bytes = fused_bytes = 0.0
        pooled = {t: {"ref": [], "bf16": []} for t in heads}
        for S in buckets:
            for B in ladder:
                ids = _ragged_ids(rng, B, S, cfg.vocab_size)
                want = {t: np.asarray(v, np.float32)
                        for t, v in ref_jit(p32, ids).items()}
                got = fused_fn(p32, ids)
                err = max(float(np.abs(np.asarray(got[t]) - want[t]).max())
                          for t in heads)
                max_err = max(max_err, err)
                got16 = fused_fn(p16, ids)
                for t in heads:
                    pooled[t]["ref"].append(want[t])
                    pooled[t]["bf16"].append(np.asarray(got16[t],
                                                        np.float32))
                us_ref = _bench(ref_jit, p32, ids, iters=iters,
                                warmup=warmup)
                us_fused = _bench(lambda i: fused_fn(p32, i), ids,
                                  iters=iters, warmup=warmup)
                ref_us_total += us_ref
                fused_us_total += us_fused
                row = {"kind": kind, "batch": B, "seq": S,
                       "f32_max_err": err, "ref_us": us_ref,
                       "fused_us": us_fused}
                if kind == "conv1d":
                    # unfused traffic: what XLA's compiled module says it
                    # moves; fused traffic: ids, the XLA gather (the
                    # embedding rows read, (B, S, E) activations written
                    # and read back by the kernel), one read of the
                    # other params, one write of the predictions
                    ub, fl = _cost_bytes_flops(apply_fn, p32, ids)
                    fb = float(KOPS.fused_forward_bytes(p32, B, S))
                    unfused_bytes += ub
                    fused_bytes += fb
                    mk = dict(arch="costmodel-conv1d", mesh="1x1",
                              chips=1, coll_bytes_per_chip=0.0,
                              flops_per_chip=fl, model_flops=fl,
                              shape=f"B{B}xS{S}")
                    r_un = RooflineReport(bytes_per_chip=ub, **mk)
                    r_fu = RooflineReport(bytes_per_chip=fb, **mk)
                    row.update(
                        unfused_bytes=ub, fused_bytes=fb,
                        traffic_reduction=ub / max(fb, 1.0),
                        unfused_roofline_fraction=r_un.roofline_fraction,
                        fused_roofline_fraction=r_fu.roofline_fraction,
                        unfused_bottleneck=r_un.bottleneck,
                        fused_bottleneck=r_fu.bottleneck)
                    _row(f"kernel_bench/{kind}/B{B}xS{S}", us_fused,
                         f"err={err:.1e}"
                         f";traffic={ub / max(fb, 1.0):.1f}x"
                         f";roofline {r_un.roofline_fraction:.3f}->"
                         f"{r_fu.roofline_fraction:.3f}")
                else:
                    _row(f"kernel_bench/{kind}/B{B}xS{S}", us_fused,
                         f"err={err:.1e};ref_us={us_ref:.0f}")
                out["shapes"].append(row)
        rho = {t: spearman(np.concatenate(pooled[t]["ref"]),
                           np.concatenate(pooled[t]["bf16"]))
               for t in heads}
        m = {"f32_max_err": max_err,
             "bf16_spearman": {t: float(r) for t, r in rho.items()},
             "bf16_spearman_min": float(min(rho.values())),
             "ref_us_total": ref_us_total,
             "fused_us_total": fused_us_total,
             "wall_ratio": ref_us_total / max(fused_us_total, 1e-9)}
        if kind == "conv1d":
            m["unfused_bytes_total"] = unfused_bytes
            m["fused_bytes_total"] = fused_bytes
            m["traffic_reduction"] = unfused_bytes / max(fused_bytes, 1.0)
        out["models"][kind] = m
        _row(f"kernel_bench/{kind}/summary", fused_us_total,
             f"max_err={max_err:.1e}"
             f";bf16_spearman_min={min(rho.values()):.4f}"
             f";wall_ratio={m['wall_ratio']:.2f}x")
    out["traffic_reduction"] = out["models"]["conv1d"]["traffic_reduction"]
    _row("kernel_bench/traffic", 0.0,
         f"aggregate={out['traffic_reduction']:.1f}x reduction "
         f"({out['models']['conv1d']['unfused_bytes_total'] / 1e6:.1f}MB->"
         f"{out['models']['conv1d']['fused_bytes_total'] / 1e6:.1f}MB)"
         f";interpret={interpret}")
    return out


# ------------------------------------------------------------ roofline_table
def roofline_table(full: bool = False, seed: int = 0,
                   dryrun_dir: str = "experiments/dryrun"):
    rows = []
    for path in sorted(glob.glob(os.path.join(dryrun_dir, "*.json"))):
        with open(path) as f:
            rec = json.load(f)
        if rec.get("status") != "ok":
            continue
        r = rec["roofline"]
        t_bound = max(r["t_compute"], r["t_memory"], r["t_collective"])
        _row(f"roofline/{rec['mesh']}/{rec['arch']}/{rec['shape']}",
             t_bound * 1e6,
             f"bottleneck={r['bottleneck']}"
             f";frac={r['roofline_fraction']:.3f}")
        rows.append(rec)
    if not rows:
        _row("roofline/none", 0.0, "no dry-run records found")
    return rows


# --------------------------------------------------------------- serve_bench
def serve_bench(full: bool = False, seed: int = 0):
    """Unified multi-target serving vs three single-target services.

    Same conv1d encoder topology and identical request stream; only the
    head layout differs. The unified service runs ONE encoder forward
    pass per graph and reads all three targets off per-target heads;
    the baseline runs the encoder once per (graph, target). Weights are
    untrained — throughput does not depend on them."""
    from repro.core import tokenizer as TOK
    from repro.core.service import CostModelService
    from repro.ir import samplers

    n_req = 512 if full else 128
    cfg = CostModelConfig(name="serve-bench", vocab_size=4096, max_seq=160,
                          embed_dim=64, conv_channels=(64,) * 6,
                          fc_dims=(256, 64))
    rng = np.random.default_rng(seed)
    graphs = [samplers.sample_graph(rng) for _ in range(n_req)]
    vocab = TOK.fit_vocab([TOK.graph_tokens(g, "ops") for g in graphs],
                          max_size=4096)
    heads = CM.DEFAULT_HEADS
    stats1 = {"mu": 0.0, "sigma": 1.0}
    key = jax.random.PRNGKey(seed)
    unified = CostModelService(
        "conv1d", cfg, CM.conv_init(key, cfg, heads=heads), vocab,
        {t: stats1 for t in heads}, mode="ops", max_seq=160)
    singles = [CostModelService(
        "conv1d", cfg, CM.conv_init(key, cfg), vocab, stats1,
        mode="ops", max_seq=160, target=t) for t in heads]

    def run_unified():
        unified._cache.clear()
        unified.predict_all(graphs)

    def run_singles():
        for s in singles:
            s._cache.clear()
            s.predict_graphs(graphs)

    iters = 10 if full else 5
    out = {}
    for name, fn in [("unified_multi_head", run_unified),
                     ("three_single_head", run_singles)]:
        fn()                           # warmup: trigger per-bucket JIT
        t0 = time.perf_counter()
        for _ in range(iters):
            fn()
        us = (time.perf_counter() - t0) / iters * 1e6
        req_s = n_req / (us / 1e6)
        out[name] = req_s
        _row(f"serve_bench/{name}", us,
             f"req_s={req_s:.0f};targets={len(heads)}")
    speedup = out["unified_multi_head"] / out["three_single_head"]
    _row("serve_bench/speedup", 0.0, f"speedup={speedup:.2f}x")
    return out


# ---------------------------------------------------------- serve_concurrent
def serve_concurrent(full: bool = False, seed: int = 0):
    """Async micro-batching gateway vs today's serialized serving, at
    matched offered load.

    At each concurrency level c, c closed-loop clients (each keeps
    exactly one request outstanding, firing the next on completion)
    push the same request stream through two serving designs:

    * ``serialized`` — the synchronous service behind one lock: every
      client's ``predict_all([g])`` is a whole batch-of-one forward
      pass, one caller at a time (the pre-server state this PR's
      motivation describes). Synchronous serving forces one OS thread
      per client — that thread count is part of the design's cost.
    * ``server`` — CostModelServer's native async API: clients are
      future callbacks (``submit`` -> resolve -> next request), no
      thread per client, and submissions coalesce into shared
      per-bucket batched forward passes.

    Weights are untrained (throughput does not depend on them); the LRU
    is cleared before every timed run and the stream has no duplicate
    graphs, so req/s measures forward-pass scheduling, not caching."""
    from repro.core import tokenizer as TOK
    from repro.core.server import CostModelServer
    from repro.core.service import CostModelService
    from repro.ir import samplers

    n_req = 2048 if full else 384
    max_batch = 64
    if full:    # paper-scale: the best Conv1D topology from §4
        cfg = CostModelConfig(name="serve-conc", vocab_size=4096,
                              max_seq=160, embed_dim=64,
                              conv_channels=(64,) * 6, fc_dims=(256, 64))
    else:       # CI-scale: narrower tower, same serving pipeline
        cfg = CostModelConfig(name="serve-conc", vocab_size=4096,
                              max_seq=160, embed_dim=48,
                              conv_filters=(2,) * 4,
                              conv_channels=(48,) * 4, fc_dims=(128, 48))
    rng = np.random.default_rng(seed)
    graphs = [samplers.sample_graph(rng) for _ in range(n_req)]
    vocab = TOK.fit_vocab([TOK.graph_tokens(g, "ops") for g in graphs],
                          max_size=4096)
    heads = CM.DEFAULT_HEADS
    stats = {t: {"mu": 0.0, "sigma": 1.0} for t in heads}
    svc = CostModelService(
        "conv1d", cfg, CM.conv_init(jax.random.PRNGKey(seed), cfg,
                                    heads=heads),
        vocab, stats, mode="ops", max_seq=160, max_batch=max_batch)
    svc.warmup()                       # AOT: no XLA compiles in timed runs

    def clear():
        with svc._cache_lock:
            svc._cache.clear()

    def drive_threads(conc, request_fn):
        """Thread-per-client closed loop (the sync design's shape)."""
        slices = [graphs[i::conc] for i in range(conc)]

        def client(gs):
            for g in gs:
                request_fn(g)

        threads = [threading.Thread(target=client, args=(s,))
                   for s in slices]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return time.perf_counter() - t0

    def drive_async(server, conc):
        """Closed loop on the async API: conc logical clients, each one
        outstanding ``submit`` whose completion callback consumes the
        prediction and fires the next request. No thread per client."""
        state_lock = threading.Lock()
        state = {"next": 0, "outstanding": 0}
        done = threading.Event()
        errors = []
        denorm = server.service.denormalize_rows

        def fail(e):
            errors.append(e)
            done.set()                 # surface, never hang the bench

        def pump():
            while True:
                with state_lock:
                    i = state["next"]
                    if i >= n_req:
                        if state["outstanding"] == 0:
                            done.set()
                        return
                    state["next"] = i + 1
                    state["outstanding"] += 1
                try:
                    fut = server.submit(graphs[i])
                except Exception as e:
                    fail(e)
                    return
                if fut.done():         # cache-hit fast path: stay inline
                    denorm(fut.result()[None])
                    with state_lock:
                        state["outstanding"] -= 1
                    continue

                def cb(f):
                    try:
                        denorm(f.result()[None])
                        with state_lock:
                            state["outstanding"] -= 1
                        pump()
                    except Exception as e:
                        fail(e)

                fut.add_done_callback(cb)
                return

        t0 = time.perf_counter()
        for _ in range(conc):
            pump()
        done.wait(timeout=300)
        if errors:
            raise errors[0]
        if not done.is_set():
            raise TimeoutError("serve_concurrent clients stalled")
        return time.perf_counter() - t0

    serial_lock = threading.Lock()

    def serialized_request(g):
        with serial_lock:              # one forward pass at a time
            svc.predict_all([g])

    out = {"n_requests": n_req, "max_batch": max_batch, "levels": {}}
    for conc in (1, 8, 64):
        clear()
        base_dt = drive_threads(conc, serialized_request)
        base_req_s = n_req / base_dt

        clear()
        server = CostModelServer(svc, max_batch=max_batch, flush_us=2000)
        server.start(warmup=False)     # service programs already warm
        dt = drive_async(server, conc)
        m = server.metrics.snapshot(server.queue_depth())
        server.stop()
        req_s = n_req / dt
        lvl = {"req_s": req_s, "serialized_req_s": base_req_s,
               "speedup_vs_serialized": req_s / base_req_s,
               "p50_us": m["latency_p50_us"], "p95_us": m["latency_p95_us"],
               "p99_us": m["latency_p99_us"],
               "batch_occupancy": m["batch_occupancy"],
               "full_flushes": m["full_flushes"],
               "deadline_flushes": m["deadline_flushes"],
               "stagnant_flushes": m["stagnant_flushes"],
               # service phase split (hash/encode/forward seconds) as
               # exported by ServerMetrics.snapshot via phase_source
               **{k: v for k, v in m.items()
                  if k.startswith("phase_")}}
        out["levels"][str(conc)] = lvl
        _row(f"serve_concurrent/serialized_c{conc}",
             base_dt / n_req * 1e6, f"req_s={base_req_s:.0f}")
        _row(f"serve_concurrent/server_c{conc}", dt / n_req * 1e6,
             f"req_s={req_s:.0f};speedup={req_s / base_req_s:.2f}x"
             f";occupancy={m['batch_occupancy']:.1f}"
             f";p50_ms={m['latency_p50_us'] / 1e3:.2f}"
             f";p99_ms={m['latency_p99_us'] / 1e3:.2f}")
    # legacy single-thread reference == serialized_c1
    out["serialized_baseline"] = {
        "req_s": out["levels"]["1"]["serialized_req_s"]}
    return out


# -------------------------------------------------------------- search_fleet
def obs_overhead(full: bool = False, seed: int = 0):
    """Cost of the unified telemetry stack on the serving hot path.

    Interleaved best-of-5 passes through the async gateway with the
    FULL obs stack on (head-sampled tracing, the metrics-registry
    JSONL exporter ticking, the drift sentinel scoring in the
    background) vs everything off — the ratio is the observability
    tax, gated in gate.py at >= 0.97x.

    The drive is *occupancy-controlled*: one client submits
    full-``max_batch`` ``predict_all`` calls serially, so every wire
    batch is exactly one full dispatch and the flush timer never
    fires. A thread-herd drive on a shared 1-core CI runner measures
    stochastic batch coalescing (~10-17% CV — scheduler noise swamps
    a 3% gate); with occupancy pinned, the off-pass CV drops to ~3%
    and the ratio actually measures the telemetry code. A separate
    pass with sampling forced to every request and 8 concurrent
    clients then checks that span trees reconstruct under contention
    (completeness >= 0.99 gate) and that drift gauges are present in
    the registry snapshot."""
    import tempfile

    from repro.core import tokenizer as TOK
    from repro.core.server import CostModelServer
    from repro.core.service import CostModelService
    from repro.ir import samplers
    from repro.obs import (JsonlExporter, MetricsRegistry, Tracer,
                           assemble, completeness, register_drift,
                           register_server, register_service,
                           register_tracer)
    from repro.obs.drift import DriftMonitor, attach

    n_req = 1280 if full else 640
    chunk = 16                         # one full wire batch per call
    conc = 8                           # completeness-pass clients
    cfg = CostModelConfig(name="obs-ovh", vocab_size=4096, max_seq=160,
                          embed_dim=48, conv_filters=(2,) * 4,
                          conv_channels=(48,) * 4, fc_dims=(128, 48))
    rng = np.random.default_rng(seed)
    graphs = [samplers.sample_graph(rng) for _ in range(n_req)]
    vocab = TOK.fit_vocab([TOK.graph_tokens(g, "ops") for g in graphs],
                          max_size=4096)
    heads = CM.DEFAULT_HEADS
    stats = {t: {"mu": 0.0, "sigma": 1.0} for t in heads}
    svc = CostModelService(
        "conv1d", cfg, CM.conv_init(jax.random.PRNGKey(seed), cfg,
                                    heads=heads),
        vocab, stats, mode="ops", max_seq=160, max_batch=chunk)
    svc.warmup()                       # AOT: no XLA compiles in timing
    chunks = [graphs[i:i + chunk] for i in range(0, n_req, chunk)]

    def clear():
        with svc._cache_lock:
            svc._cache.clear()

    def drive(server):
        """Occupancy-controlled closed loop on the traced entry point
        (``predict_all``, where sampling, span creation and the drift
        hook live): each call is one full wire batch, so the flush
        timer never fires and batch coalescing is deterministic."""
        t0 = time.perf_counter()
        for c in chunks:
            server.predict_all(c)
        return n_req / (time.perf_counter() - t0)

    def run_pass(obs_on: bool, tmpdir: str, rep: int):
        tracer = drift = exporter = None
        if obs_on:
            tracer = Tracer(sample_every=4)
            drift = attach(svc, DriftMonitor(sample_every=8))
            reg = MetricsRegistry()
            register_service(reg, svc)
            register_drift(reg, drift)
            register_tracer(reg, tracer)
            exporter = JsonlExporter(
                os.path.join(tmpdir, f"obs_{rep}.jsonl"), reg,
                tracer=tracer, interval_s=0.25)
        server = CostModelServer(svc, max_batch=chunk, flush_us=2000,
                                 tracer=tracer)
        if obs_on:
            register_server(reg, server)
            exporter.start()
        server.start(warmup=False)
        clear()
        try:
            req_s = drive(server)
        finally:
            server.stop()
            if obs_on:
                drift.stop()           # drains + scores its queue
                exporter.stop()
                svc.drift = None       # next OFF pass pays nothing
        lines = exporter.lines_written if obs_on else 0
        scored = drift.scored if obs_on else 0
        return req_s, lines, scored

    off_s, on_s, jsonl_lines, drift_scored = [], [], 0, 0
    with tempfile.TemporaryDirectory() as tmpdir:
        run_pass(False, tmpdir, 98)    # untimed warmups, both paths
        run_pass(True, tmpdir, 99)
        for rep in range(5):           # interleaved best-of-5
            r, _, _ = run_pass(False, tmpdir, rep)
            off_s.append(r)
            r, ln, sc = run_pass(True, tmpdir, rep)
            on_s.append(r)
            jsonl_lines, drift_scored = ln, sc

    ratio = max(on_s) / max(off_s)
    _row("obs_overhead/off", 1e6 / max(off_s),
         f"req_s={max(off_s):.0f}")
    _row("obs_overhead/on", 1e6 / max(on_s),
         f"req_s={max(on_s):.0f};ratio={ratio:.3f}"
         f";jsonl_lines={jsonl_lines};drift_scored={drift_scored}")

    # trace-completeness pass: force-sample EVERY request, then check
    # the span trees reconstruct end to end
    tracer = Tracer(sample_every=1)
    drift = attach(svc, DriftMonitor(sample_every=4))
    server = CostModelServer(svc, max_batch=64, flush_us=2000,
                             tracer=tracer)
    server.start(warmup=False)
    clear()
    try:
        sub = graphs[:min(128, n_req)]
        slices = [sub[i::8] for i in range(8)]
        threads = [threading.Thread(
            target=lambda gs: [server.predict_all([g]) for g in gs],
            args=(s,)) for s in slices]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    finally:
        server.stop()
        drift.stop()
        svc.drift = None
    trees = assemble(tracer.recorder.snapshot())
    comp = completeness(trees)
    reg = MetricsRegistry()
    register_drift(reg, drift)
    snap = reg.snapshot()["metrics"]
    want = {"drift.oov_rate"} | {f"drift.spearman.{t}" for t in heads}
    gauges_present = want <= set(snap)
    _row("obs_overhead/trace", 0.0,
         f"traces={len(trees)};completeness={comp:.3f}"
         f";drift_gauges={int(gauges_present)}")
    return {"n_requests": n_req, "concurrency": conc,
            "req_s_off": max(off_s), "req_s_on": max(on_s),
            "overhead_ratio": ratio,
            "jsonl_lines": jsonl_lines, "drift_scored": drift_scored,
            "trace": {"n_traces": len(trees),
                      "completeness": comp},
            "drift_gauges_present": gauges_present}


def _unoptimized_ir(g, rng):
    """Dress a sampled graph up as the *unoptimized* IR a compiler
    hands the optimizer: naive elementwise chains (fusion fodder),
    duplicated subexpressions (CSE fodder), and dead ops (DCE
    fodder), so every search has a rich rewrite frontier instead of
    the handful of sites already-clean graphs expose."""
    from repro.ir import graph as IRG
    from repro.ir.graph import ELEMENTWISE, Tensor
    ew = sorted(ELEMENTWISE)
    new = IRG.Graph(name=g.name + "_raw")
    new.values = list(g.values[:g.n_args])
    new.n_args = g.n_args
    for op in g.ops:
        new.add_op(op.opcode, list(op.operands),
                   g.values[op.result], **op.attrs)
    new.outputs = list(g.outputs)
    results = [op.result for op in new.ops]
    for _ in range(6):               # fusable chains ending in outputs
        v = results[int(rng.integers(0, len(results)))]
        for _ in range(int(rng.integers(3, 7))):
            t = new.values[v]
            v = new.add_op(ew[int(rng.integers(0, len(ew)))], [v],
                           Tensor(t.shape, t.dtype))
        new.outputs.append(v)
    for _ in range(4):               # duplicate subexpressions (CSE)
        op = new.ops[int(rng.integers(0, len(new.ops)))]
        d = new.add_op(op.opcode, list(op.operands),
                       new.values[op.result], **op.attrs)
        t = new.values[d]
        new.outputs.append(
            new.add_op("relu", [d], Tensor(t.shape, t.dtype)))
    for _ in range(3):               # dead ops (DCE)
        v = results[int(rng.integers(0, len(results)))]
        t = new.values[v]
        new.add_op("exp", [v], Tensor(t.shape, t.dtype))
    new.validate()
    return new


def _fleet_fixture(full: bool, seed: int):
    """Shared pool / vocab / params / knobs for the fleet benches
    (search_fleet and search_fleet_replicated run identical work)."""
    from repro.core import tokenizer as TOK
    from repro.core.service import CostModelService
    from repro.ir import samplers
    from repro.opt import rewrites as RW

    n_workers = 12 if full else 8
    n_pool = 10 if full else 5
    beam, steps, budget = (4, 4, 128) if full else (4, 3, 64)
    max_batch = 32
    cfg = CostModelConfig(name="fleet", vocab_size=4096, max_seq=256,
                          embed_dim=48, conv_filters=(2,) * 4,
                          conv_channels=(48,) * 4, fc_dims=(128, 48))
    rng = np.random.default_rng(seed)
    fams = sorted(samplers.SAMPLERS)
    pool = [_unoptimized_ir(
        samplers.sample_graph(rng, fams[i % len(fams)]), rng)
        for i in range(n_pool)]
    # vocab over the pool + rewritten variants, so fused/bf16 candidate
    # text is in-vocabulary (as a rewrite_factor training corpus would be)
    vocab_seqs = [TOK.graph_tokens(g, "ops") for g in pool]
    vocab_seqs += [TOK.graph_tokens(RW.random_rewrite(g, rng), "ops")
                   for g in pool for _ in range(3)]
    vocab = TOK.fit_vocab(vocab_seqs, max_size=4096)
    heads = CM.DEFAULT_HEADS
    params = CM.conv_init(jax.random.PRNGKey(seed), cfg, heads=heads)
    stats = {t: {"mu": 0.0, "sigma": 1.0} for t in heads}

    def make_service(**kw):
        return CostModelService("conv1d", cfg, params, vocab, stats,
                                mode="ops", max_seq=256,
                                max_batch=max_batch,
                                buckets=(64, 128, 256),
                                batch_ladder=(1, 2, 4, 8, 16, 32), **kw)

    return {"n_workers": n_workers, "n_pool": n_pool, "beam": beam,
            "steps": steps, "budget": budget, "max_batch": max_batch,
            "cfg": cfg, "pool": pool, "vocab": vocab, "heads": heads,
            "params": params, "stats": stats,
            "make_service": make_service}


def search_fleet(full: bool = False, seed: int = 0):
    """Fleet-scale concurrent search: N beam_search workers drive ONE
    async micro-batching CostModelServer gateway.

    Workers optimize the same graph pool in rotated order, the
    compiler-fleet shape the server was built for: different searches
    re-derive the same candidates, so requests coalesce in flight and
    cross-search LRU hits dominate — exactly the regime where candidate
    *featurization* (struct hashing + tokenization + encoding), not the
    forward pass, is the hot path. The fleet runs twice over identical
    work:

    * ``fast``     — incremental struct hashing (rewrites thread parent
      hash memos), key-first LRU probes, ids cache + parent-delta token
      splicing, vectorized encode_many.
    * ``baseline`` — both switched off (``set_incremental_hashing(False)``
      + ``fast_encode=False``): every candidate pays a full SHA-1 Merkle
      walk per struct_key call and a full re-lex + dict.get encode, the
      pre-incremental behavior.

    Reports candidates-costed/s per mode (gate: fast >= ~2x baseline),
    cache/dedup hit rates, batch occupancy, the tokenize/encode/hash vs
    forward wall-clock split, and bf16-vs-f32 serving drift (gate:
    Spearman >= 0.99 per target on the candidate corpus). Weights are
    untrained — throughput and drift ranking do not depend on them."""
    from repro.core.server import CostModelServer
    from repro.ir import graph as IRG
    from repro.opt import rewrites as RW
    from repro.opt import search as OS

    fx = _fleet_fixture(full, seed)
    n_workers, n_pool = fx["n_workers"], fx["n_pool"]
    beam, steps, budget = fx["beam"], fx["steps"], fx["budget"]
    max_batch, pool, heads = fx["max_batch"], fx["pool"], fx["heads"]
    make_service = fx["make_service"]

    def run_fleet(svc):
        """Drive the full fleet once; returns (wall_s, candidates, mode
        metrics). Caller owns warmup/cache state."""
        server = CostModelServer(svc, max_batch=max_batch,
                                 flush_us=150)
        server.start(warmup=False)
        results, errs = [], []

        def worker(w):
            try:
                gs = pool[w % n_pool:] + pool[:w % n_pool]
                for g in gs:
                    results.append(OS.beam_search(
                        server, g, beam_width=beam, max_steps=steps,
                        eval_budget=budget))
            except Exception as e:       # surface, don't hang the bench
                errs.append(e)

        threads = [threading.Thread(target=worker, args=(w,))
                   for w in range(n_workers)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        dt = time.perf_counter() - t0
        if errs:
            raise errs[0]
        m = server.metrics.snapshot(server.queue_depth())
        server.stop()
        # every evaluated candidate plus each search's root was costed
        cands = sum(r.evaluated + 1 for r in results)
        return dt, cands, m

    out = {"n_workers": n_workers, "n_pool": n_pool,
           "searches": n_workers * n_pool, "beam": beam,
           "max_steps": steps, "eval_budget": budget, "modes": {}}

    def _fleet_pass(mode, svc):
        """One fleet pass under the mode's hashing flag; returns
        (wall, candidates, server metrics, phase delta)."""
        prev = IRG.set_incremental_hashing(mode == "fast")
        try:
            with svc._cache_lock:
                svc._phase_s = {k: 0.0 for k in svc._phase_s}
            dt, cands, m = run_fleet(svc)
            return dt, cands, m, svc.phase_stats()
        finally:
            IRG.set_incremental_hashing(prev)

    modes = ("fast", "baseline")
    svcs, cold, cstats = {}, {}, {}
    for mode in modes:
        svc = make_service(fast_encode=(mode == "fast"))
        svc.warmup()                     # AOT: no XLA compiles when timed
        _fleet_pass(mode, svc)           # untimed: python warm
        with svc._cache_lock:            # cold pass starts cache-cold
            svc._cache.clear()
            svc._ids_cache.clear()
        # cold pass: compulsory misses — forward passes, batching and
        # cross-search dedup are all on the clock
        cold[mode] = _fleet_pass(mode, svc)
        cstats[mode] = svc.cache_stats()
        svcs[mode] = svc
    # steady passes: caches stay warm (the long-running-fleet regime the
    # hot path is built for) — every candidate is still re-derived,
    # re-hashed, and re-featurized per search, but predictions answer
    # from the shared LRU, so the clock isolates exactly the
    # per-candidate featurization cost the incremental path removes.
    # Modes alternate (fast, baseline, fast, ...) and take best-of-3 so
    # load drift on a shared runner hits both modes alike.
    steady = {m: None for m in modes}
    for _ in range(3):
        for mode in modes:
            d, c, _, p = _fleet_pass(mode, svcs[mode])
            if steady[mode] is None or c / d > \
                    steady[mode][1] / steady[mode][0]:
                steady[mode] = (d, c, p)

    def _worker_pass(mode):
        """One single-worker pass over the warm pool, through the
        gateway -> candidates/s. The search loop is GIL-bound python, so
        aggregate fleet candidates/s tracks per-worker per-candidate
        cost — and single-threaded passes resist shared-runner scheduler
        noise far better than N-thread wall clock. Best-of-3, modes
        interleaved (same rationale as the fleet steady passes), is the
        gated speedup; the fleet wall ratios are reported alongside."""
        svc = svcs[mode]
        prev = IRG.set_incremental_hashing(mode == "fast")
        try:
            server = CostModelServer(svc, max_batch=max_batch,
                                     flush_us=150)
            server.start(warmup=False)
            cands = 0
            t0 = time.perf_counter()
            for g in pool:
                r = OS.beam_search(server, g, beam_width=beam,
                                   max_steps=steps, eval_budget=budget)
                cands += r.evaluated + 1
            dt = time.perf_counter() - t0
            server.stop()
            return cands / dt
        finally:
            IRG.set_incremental_hashing(prev)

    worker_cps = {m: 0.0 for m in modes}
    for _ in range(3):
        for mode in modes:
            worker_cps[mode] = max(worker_cps[mode], _worker_pass(mode))
    for mode in modes:
        dt_c, cands_c, m, phase = cold[mode]
        dt_s, cands_s, phase_s = steady[mode]
        st = cstats[mode]
        featurize_s = phase["hash_s"] + phase["encode_s"]
        rec = {"cold": {"wall_s": dt_c, "candidates_costed": cands_c,
                        "candidates_per_s": cands_c / dt_c},
               "steady": {"wall_s": dt_s, "candidates_costed": cands_s,
                          "candidates_per_s": cands_s / dt_s,
                          "hash_s": phase_s["hash_s"],
                          "encode_s": phase_s["encode_s"]},
               "phase_split": {
                   "hash_s": phase["hash_s"],
                   "encode_s": phase["encode_s"],
                   "forward_s": phase["forward_s"],
                   "featurize_frac_of_wall": featurize_s / dt_c},
               "lru_hit_rate": st["hit_rate"],
               "ids_cache_hit_rate": st["ids_hit_rate"],
               "delta_encodes": phase["delta_encodes"],
               "full_encodes": phase["full_encodes"],
               "truncations": st["truncations"],
               "server": {"requests": m["requests"],
                          "cache_hit_rate": m["cache_hit_rate"],
                          "coalesced": m["coalesced"],
                          "batches": m["batches"],
                          "batch_occupancy": m["batch_occupancy"],
                          # service phase split as exported by
                          # ServerMetrics.snapshot (phase_source)
                          **{k: v for k, v in m.items()
                             if k.startswith("phase_")}}}
        out["modes"][mode] = rec
        _row(f"search_fleet/{mode}_cold", dt_c / cands_c * 1e6,
             f"cands_s={cands_c / dt_c:.0f};workers={n_workers}"
             f";lru_hit={st['hit_rate']:.1%}"
             f";occupancy={m['batch_occupancy']:.1f}"
             f";hash_ms={phase['hash_s'] * 1e3:.0f}"
             f";encode_ms={phase['encode_s'] * 1e3:.0f}"
             f";forward_ms={phase['forward_s'] * 1e3:.0f}")
        _row(f"search_fleet/{mode}_steady", dt_s / cands_s * 1e6,
             f"cands_s={cands_s / dt_s:.0f}"
             f";hash_ms={phase_s['hash_s'] * 1e3:.0f}"
             f";encode_ms={phase_s['encode_s'] * 1e3:.0f}")
    speedup = worker_cps["fast"] / worker_cps["baseline"]
    fleet_speedup = (out["modes"]["fast"]["steady"]["candidates_per_s"]
                     / out["modes"]["baseline"]["steady"]
                     ["candidates_per_s"])
    cold_speedup = (out["modes"]["fast"]["cold"]["candidates_per_s"]
                    / out["modes"]["baseline"]["cold"]["candidates_per_s"])
    out["per_worker_steady_cands_s"] = worker_cps
    out["speedup_vs_baseline"] = speedup      # per-worker steady (gated)
    out["fleet_steady_speedup_vs_baseline"] = fleet_speedup
    out["cold_speedup_vs_baseline"] = cold_speedup
    _row("search_fleet/speedup", 0.0,
         f"per_worker_steady={speedup:.2f}x"
         f";fleet_steady={fleet_speedup:.2f}x;cold={cold_speedup:.2f}x")

    # bf16 serving drift vs f32 on the fleet's candidate corpus: same
    # params, bf16-cast once; gate.py enforces Spearman >= 0.99/target
    corpus = list(pool)
    crng = np.random.default_rng(seed + 7)
    corpus += [RW.random_rewrite(g, crng) for g in pool for _ in range(5)]
    # tie-averaging + degenerate-safe rank correlation (0.0, not NaN,
    # when a head collapses — a NaN must not slip past the drift gate)
    from repro.opt.evaluate import spearman
    svc_f32 = make_service()
    svc_bf16 = make_service(dtype="bf16")
    p32 = svc_f32.predict_all(corpus)
    pbf = svc_bf16.predict_all(corpus)

    drift = {"spearman": {}, "max_rel_err": {}}
    for t in heads:
        drift["spearman"][t] = spearman(p32[t], pbf[t])
        rel = np.abs(pbf[t] - p32[t]) / np.maximum(np.abs(p32[t]), 1e-9)
        drift["max_rel_err"][t] = float(rel.max())
    drift["spearman_min"] = min(drift["spearman"].values())
    drift["max_rel_err_all"] = max(drift["max_rel_err"].values())
    out["bf16"] = drift
    _row("search_fleet/bf16_drift", 0.0,
         f"spearman_min={drift['spearman_min']:.4f}"
         f";max_rel_err={drift['max_rel_err_all']:.4f}"
         f";corpus={len(corpus)}")
    return out


# --------------------------------------------------- search_fleet_replicated
def search_fleet_replicated(full: bool = False, seed: int = 0,
                            replicas: int = 4):
    """Replicated serving tier vs the thread fleet, on identical work.

    * ``baseline`` — today's pre-replication worst case: N *thread*
      workers convoying on the GIL through one in-process gateway, with
      incremental hashing and fast_encode both off (every candidate
      re-hashed and re-encoded from scratch).
    * ``replicated`` — N *process* workers, each a persistent
      :class:`~repro.serving.router.ReplicaClient` (GIL-free search +
      featurization + local LRU), routing misses by struct key across
      ``replicas`` spawned model replicas with adaptive flush deadlines
      and a shared cross-replica cache behind them.

    Both run the same pool / rotation / search parameters: a warm
    (untimed) pass, a cache-cold timed pass, then best-of-3 steady
    passes (each steady pass repeats the pool 3x inside one timed
    window, so the per-pass barrier does not pollute the short
    steady measurement). gate.py enforces replicated steady >= 3x
    baseline steady
    locally (>= 2x on shared CI runners) at replicas >= 4. Also reports
    per-replica LRU hit rates (struct-key routing should keep each
    replica's working set disjoint and hot), router health, shared-tier
    hits, and the adaptive effective-flush gauge."""
    from repro.core.server import CostModelServer
    from repro.ir import graph as IRG
    from repro.opt import search as OS
    from repro.serving import FleetDriver, ServiceSpec, start_replicas

    fx = _fleet_fixture(full, seed)
    n_workers, pool = fx["n_workers"], fx["pool"]
    search_kw = {"beam_width": fx["beam"], "max_steps": fx["steps"],
                 "eval_budget": fx["budget"]}
    max_batch = fx["max_batch"]
    out = {"n_workers": n_workers, "n_pool": fx["n_pool"],
           "replicas": replicas, "modes": {}, **search_kw}

    # ---- baseline: thread fleet, from-scratch featurization ----------
    svc = fx["make_service"](fast_encode=False)
    svc.warmup()

    def _thread_pass(rounds=1):
        prev = IRG.set_incremental_hashing(False)
        try:
            server = CostModelServer(svc, max_batch=max_batch,
                                     flush_us=150)
            server.start(warmup=False)
            results, errs = [], []

            def worker(w):
                try:
                    for _ in range(rounds):
                        results.extend(OS.search_pool(
                            server, pool, offset=w, **search_kw))
                except Exception as e:
                    errs.append(e)

            threads = [threading.Thread(target=worker, args=(w,))
                       for w in range(n_workers)]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            dt = time.perf_counter() - t0
            server.stop()
            if errs:
                raise errs[0]
            return dt, sum(r.evaluated + 1 for r in results)
        finally:
            IRG.set_incremental_hashing(prev)

    def _clear_base():
        with svc._cache_lock:
            svc._cache.clear()
            svc._ids_cache.clear()

    # steady passes repeat the pool STEADY_ROUNDS times inside one
    # timed pass (both modes): the measurement window grows ~3x while
    # the per-pass setup/barrier cost is paid once, which keeps
    # scheduler noise on a busy host out of the gated ratio
    STEADY_ROUNDS = 3
    _thread_pass()                     # python-warm, untimed
    _clear_base()
    base_cold = _thread_pass()
    base_steady = base_cold
    for _ in range(3):
        d, c = _thread_pass(rounds=STEADY_ROUNDS)
        if c / d > base_steady[1] / base_steady[0]:
            base_steady = (d, c)

    # ---- replicated: process fleet behind the router -----------------
    spec = ServiceSpec.from_service(fx["make_service"](fast_encode=True))
    tier = start_replicas(spec, replicas, n_clients=n_workers,
                          max_batch=max_batch, flush_us=150.0,
                          adaptive_flush=True)
    try:
        driver = FleetDriver.start(tier, pool, n_workers,
                                   search_kw=search_kw)
        try:
            driver.run_pass()          # warm, untimed
            driver.clear()
            tier.shared_cache.clear()
            rep_cold = driver.run_pass()
            rep_steady = rep_cold
            for _ in range(3):
                p = driver.run_pass(rounds=STEADY_ROUNDS)
                if p["candidates"] / p["wall_s"] > \
                        rep_steady["candidates"] / rep_steady["wall_s"]:
                    rep_steady = p
            stats = driver.stats(include_replicas=True)
        finally:
            driver.stop()
    finally:
        tier.stop()

    def _cps(dt, cands):
        return cands / dt

    out["modes"]["baseline"] = {
        "cold": {"wall_s": base_cold[0], "candidates": base_cold[1],
                 "candidates_per_s": _cps(*base_cold)},
        "steady": {"wall_s": base_steady[0], "candidates": base_steady[1],
                   "candidates_per_s": _cps(*base_steady)}}
    rep_rec = {
        "cold": {"wall_s": rep_cold["wall_s"],
                 "candidates": rep_cold["candidates"],
                 "candidates_per_s": rep_cold["candidates"]
                 / rep_cold["wall_s"]},
        "steady": {"wall_s": rep_steady["wall_s"],
                   "candidates": rep_steady["candidates"],
                   "candidates_per_s": rep_steady["candidates"]
                   / rep_steady["wall_s"]}}
    replica_stats = (stats[0] or {}).get("replicas") or []
    per_replica = []
    for payload in replica_stats:
        if not payload:
            continue
        s, c = payload["server"], payload["cache"]
        per_replica.append({
            "replica_id": payload["replica_id"],
            "requests": s["requests"],
            "batches": s["batches"],
            "batch_occupancy": s["batch_occupancy"],
            "lru_hit_rate": c["hit_rate"],
            "lru_size": c["size"],
            "flush_us_effective": s.get("flush_us_effective"),
            "shared_hits": payload["shared_hits"],
            "shared_misses": payload["shared_misses"],
            **{k: v for k, v in s.items() if k.startswith("phase_")}})
    rep_rec["per_replica"] = per_replica
    rep_rec["router"] = {
        "shed_total": sum(w["shed_count"] for w in stats if w),
        "health": [w["health"] for w in stats if w],
        "local_hit_rates": [w["local_cache"]["hit_rate"]
                            for w in stats if w]}
    rep_rec["shared_cache_fill"] = tier.shared_cache.fill()
    out["modes"]["replicated"] = rep_rec

    steady_ratio = (rep_rec["steady"]["candidates_per_s"]
                    / out["modes"]["baseline"]["steady"]
                    ["candidates_per_s"])
    cold_ratio = (rep_rec["cold"]["candidates_per_s"]
                  / out["modes"]["baseline"]["cold"]["candidates_per_s"])
    out["replicated_steady_speedup_vs_baseline"] = steady_ratio
    out["replicated_cold_speedup_vs_baseline"] = cold_ratio
    for mode in ("baseline", "replicated"):
        for ph in ("cold", "steady"):
            r = out["modes"][mode][ph]
            _row(f"search_fleet_replicated/{mode}_{ph}",
                 r["wall_s"] / r["candidates"] * 1e6,
                 f"cands_s={r['candidates_per_s']:.0f}"
                 f";workers={n_workers};replicas="
                 f"{replicas if mode == 'replicated' else 0}")
    hits = [f"{r['lru_hit_rate']:.0%}" for r in per_replica]
    _row("search_fleet_replicated/speedup", 0.0,
         f"steady={steady_ratio:.2f}x;cold={cold_ratio:.2f}x"
         f";replica_lru_hits={'/'.join(hits)}"
         f";shed={rep_rec['router']['shed_total']}")
    return out


# ---------------------------------------------------------------- opt_search
def opt_search(full: bool = False, seed: int = 0):
    """Cost-model-guided beam search (repro.opt) vs the one-shot
    FusionAdvisor baseline, judged by the ir/analyzers oracle.

    Trains a joint multi-target conv1d model on a rewrite-augmented
    corpus (so fused / bf16 IR text is in-vocabulary), serves it behind
    the async micro-batching CostModelServer, then beam-searches rewrite
    sequences over graphs sampled from all five families. Reports search
    throughput (graphs/s — every frontier expansion is ONE batched
    predict_all through the server) and oracle latency improvement;
    ``gate.py`` soft-gates beam improvement >= the baseline's."""
    from repro.core.server import CostModelServer
    from repro.core.service import CostModelService
    from repro.ir import samplers
    from repro.opt import evaluate as OE
    from repro.opt import search as OS

    n_train = 3000 if full else 700
    steps = 700 if full else 250
    n_eval = 50 if full else 20
    cfg = CostModelConfig(name="opt-bench", vocab_size=4096, max_seq=160,
                          embed_dim=64, conv_channels=(64,) * 6,
                          fc_dims=(256, 64))
    ds = DS.build_dataset(n_train, mode="ops", max_seq=160,
                          vocab_size=4096, augment_factor=1,
                          rewrite_factor=1, seed=seed)
    tr, _ = ds.split(0.1)
    t0 = time.time()
    res = TR.TrainEngine("conv1d", cfg, CM.DEFAULT_HEADS, steps=steps,
                         batch_size=128, lr=2e-3, seed=seed).fit(tr)
    _row("opt_search/train", (time.time() - t0) * 1e6,
         f"steps={steps};rows={len(tr)}")
    svc = CostModelService("conv1d", cfg, res.params, ds.vocab,
                           res.norm_stats, mode="ops", max_seq=160)
    rng = np.random.default_rng(seed + 1)
    fams = sorted(samplers.SAMPLERS)
    graphs = [samplers.sample_graph(rng, fams[i % len(fams)])
              for i in range(n_eval)]
    with CostModelServer(svc, max_batch=64, flush_us=1000) as server:
        t0 = time.perf_counter()
        report = OE.evaluate_search(
            server, graphs, objective=OS.Objective(),
            beam_width=4 if full else 3, max_steps=6 if full else 4,
            eval_budget=256 if full else 128)
        dt = time.perf_counter() - t0
        metrics = server.metrics.snapshot()
    phase = svc.phase_stats()
    s = report["summary"]
    throughput = n_eval / dt
    _row("opt_search/phase_split", 0.0,
         f"hash_ms={phase['hash_s'] * 1e3:.0f}"
         f";encode_ms={phase['encode_s'] * 1e3:.0f}"
         f";forward_ms={phase['forward_s'] * 1e3:.0f}"
         f";delta_encodes={phase['delta_encodes']}"
         f";full_encodes={phase['full_encodes']}")
    _row("opt_search/beam", dt / n_eval * 1e6,
         f"graphs_s={throughput:.2f}"
         f";oracle_impr={s['oracle_improvement_mean']:.1%}"
         f";fuse_baseline={s['baseline_oracle_improvement_mean']:.1%}"
         f";beat_baseline={s['frac_strictly_better_than_baseline']:.0%}")
    _row("opt_search/model_fidelity", 0.0,
         f"pred_impr={s['pred_improvement_mean']:.1%}"
         f";spearman_within={s['spearman_pred_oracle']:.3f}"
         f";spearman_pooled={s['spearman_pred_oracle_pooled']:.3f}"
         f";candidates={s['candidates_costed']}"
         f";predict_calls={s['predict_calls']}")
    return {"n_eval": n_eval, "throughput_graphs_s": throughput,
            "summary": s, "per_graph": report["per_graph"],
            "phase_split": {k: phase[k]
                            for k in ("hash_s", "encode_s", "forward_s",
                                      "delta_encodes", "full_encodes")},
            "server": {k: metrics[k] for k in
                       ("requests", "batches", "batch_occupancy",
                        "cache_hit_rate")}}


# --------------------------------------------------------------- train_bench
def train_bench(full: bool = False, seed: int = 0):
    """TrainEngine bucketed batching vs max_seq padding on a mixed-length
    corpus: steady-state steps/s (median step time, robust to per-bucket
    compile spikes) and per-target eval parity on the same seed.

    ``bucketed`` is the engine default (batch_max: identical batch
    composition, per-batch bucket pad width — gradient-identical to the
    padded baseline, so eval metrics match to float noise).
    ``bucketed_homogeneous`` single-bucket batches are the throughput
    ceiling; their batches are length-correlated, so eval parity is NOT
    claimed for them (see data/pipeline.py)."""
    n = 4000 if full else 1000
    steps = 400 if full else 160
    cfg = CostModelConfig(name="train-bench", vocab_size=4096, max_seq=256,
                          embed_dim=64, conv_channels=(64,) * 6,
                          fc_dims=(256, 64))
    ds = DS.build_dataset(n, mode="ops", max_seq=256, vocab_size=4096,
                          augment_factor=2, seed=seed)
    tr, te = ds.split(0.1)
    out = {}
    runs = [("padded_max_seq", dict(bucketed=False)),
            ("bucketed", dict(bucketed=True)),
            ("bucketed_homogeneous",
             dict(bucketed=True, bucket_mode="homogeneous",
                  drop_remainder=False))]   # tails: every bucket trains
    for name, kw in runs:
        dts = []
        eng = TR.TrainEngine("conv1d", cfg, "register_pressure",
                             steps=steps, batch_size=64, lr=2e-3,
                             seed=seed, **kw)
        res = eng.fit(tr, on_step=lambda s, dt: dts.append(dt))
        m = TR.evaluate("conv1d", cfg, res, te, "register_pressure")
        med = float(np.median(dts))
        out[name] = {"steps_per_s": 1.0 / med, "metrics": m}
        _row(f"train_bench/{name}", med * 1e6,
             f"steps_s={1.0 / med:.1f}"
             f";rmse_rel={m['rmse_rel_pct']:.2f}%"
             f";exact={m['exact_pct']:.1f}%")
    for name in ["bucketed", "bucketed_homogeneous"]:
        speedup = out[name]["steps_per_s"] / \
            out["padded_max_seq"]["steps_per_s"]
        _row(f"train_bench/speedup_{name}", 0.0, f"speedup={speedup:.2f}x")
    return out


# ------------------------------------------------- transformer_extension
def transformer_extension(full: bool = False, seed: int = 0):
    """Beyond-paper: the paper's §6 future-work #1 (Transformer cost
    model) head-to-head with its best Conv1D model."""
    n = 6000 if full else 1200
    steps = 1200 if full else 300
    cfg = CostModelConfig(name="bench-xf", vocab_size=2048, max_seq=128,
                          embed_dim=64, conv_channels=(64,) * 6,
                          fc_dims=(128, 64))
    ds = DS.build_dataset(n, mode="ops", max_seq=128, vocab_size=2048,
                          augment_factor=2, seed=seed)
    tr, te = ds.split(0.1)
    out = {}
    for kind in ["conv1d", "xformer"]:
        t0 = time.time()
        res = TR.TrainEngine(kind, cfg, "register_pressure",
                             steps=steps, batch_size=64,
                             lr=2e-3 if kind == "conv1d" else 1e-3,
                             seed=seed).fit(tr)
        m = TR.evaluate(kind, cfg, res, te, "register_pressure")
        out[kind] = m
        _row(f"transformer_extension/{kind}", (time.time() - t0) * 1e6,
             f"rmse_rel={m['rmse_rel_pct']:.2f}%"
             f";within5={m['within5_pct']:.1f}%")
    return out


# --------------------------------------------------------------- ingest
def ingest(full: bool = False, seed: int = 0):
    """Real-MLIR front door: arch-corpus ingestion throughput plus fuzz
    robustness.

    Lowers the per-layer StableHLO subgraphs of real architectures from
    ``repro.configs.ARCHS``, pushes every text through
    ``CostModelService.predict_text`` with an OOV-extended vocab (hash
    unk shards + byte fallback), then a seeded fuzz corpus of >= 200
    mutated/truncated/dialect-spliced texts. ``gate.py`` hard-gates
    zero uncaught exceptions, zero arch-corpus ingest errors, and zero
    collapse onto bare ``<unk>``."""
    from repro.core import tokenizer as TOKZ
    from repro.core.service import CostModelService
    from repro.ir import frontdoor as FD
    from repro.ir import samplers
    from repro.ir import stablehlo as SH

    names = None if full else ["qwen3-0.6b", "xlstm-125m",
                               "whisper-small", "granite-moe-1b-a400m",
                               "starcoder2-3b"]
    t0 = time.perf_counter()
    corpus = SH.lower_arch_corpus(names, seq=8)
    lower_s = time.perf_counter() - t0

    cfg = CostModelConfig(name="bench-ingest", vocab_size=1024,
                          max_seq=256, embed_dim=16,
                          conv_channels=(16,) * 2, fc_dims=(32,))
    rng = np.random.default_rng(seed)
    seqs = [TOKZ.graph_tokens(samplers.sample_graph(rng), "ops")
            for _ in range(16)]
    vocab = TOKZ.extend_vocab_oov(
        TOKZ.fit_vocab(seqs, max_size=600), n_unk_buckets=32,
        byte_fallback=True, max_size=cfg.vocab_size)
    params = CM.conv_init(jax.random.PRNGKey(seed), cfg,
                          heads=CM.DEFAULT_HEADS)
    stats = {t: {"mu": 0.2, "sigma": 1.3} for t in CM.DEFAULT_HEADS}
    svc = CostModelService("conv1d", cfg, params, vocab, stats,
                           mode="ops", max_seq=256)

    texts = [t for _, _, t in corpus]
    t0 = time.perf_counter()
    outs = [svc.predict_text(t) for t in texts]
    arch_dt = time.perf_counter() - t0
    preds = [o for o in outs if not isinstance(o, FD.IngestError)]
    arch = {
        "texts": len(texts),
        "errors": len(outs) - len(preds),
        "unk_rate_max": max((p.unk_rate for p in preds), default=1.0),
        "oov_rate_mean": float(np.mean([p.oov_rate for p in preds]))
        if preds else 1.0,
        "texts_per_s": len(texts) / arch_dt if arch_dt else 0.0,
    }
    _row("ingest/arch_corpus", arch_dt / max(len(texts), 1) * 1e6,
         f"texts={arch['texts']};errors={arch['errors']}"
         f";unk_max={arch['unk_rate_max']:.2f}"
         f";oov_mean={arch['oov_rate_mean']:.2f}")

    n_fuzz = 400 if full else 200
    mutated = FD.fuzz_corpus(texts, n_fuzz,
                             np.random.default_rng(seed + 1))
    ok = err = uncaught = 0
    t0 = time.perf_counter()
    for t in mutated:
        try:
            out = svc.predict_text(t)
            if isinstance(out, FD.IngestError):
                err += 1
            else:
                ok += 1
        except Exception:
            uncaught += 1
    fuzz_dt = time.perf_counter() - t0
    fuzz = {"n": len(mutated), "predictions": ok,
            "structured_errors": err, "uncaught": uncaught,
            "texts_per_s": len(mutated) / fuzz_dt if fuzz_dt else 0.0}
    _row("ingest/fuzz", fuzz_dt / max(len(mutated), 1) * 1e6,
         f"n={fuzz['n']};ok={ok};err={err};uncaught={uncaught}")

    ps = svc.phase_stats()
    return {"archs": len({a for a, _, _ in corpus}),
            "lower_s": lower_s, "arch": arch, "fuzz": fuzz,
            "service_oov_rate": ps["oov_rate"],
            "ingest_errors": ps["ingest_errors"]}


# ---------------------------------------------------------- chaos_serve
def chaos_serve(full: bool = False, seed: int = 0):
    """Supervised replicated tier under a seeded, scripted fault plan.

    A 2-replica tier with a :class:`ReplicaSupervisor` serves a
    closed-loop request stream through a :class:`FaultyTransport`
    whose :class:`FaultPlan` (clocked by the send-op counter, so the
    schedule replays byte-for-byte) covers every fault seam: SIGKILL a
    replica, SIGSTOP-wedge the other (the heartbeat's job to catch),
    drop/delay/duplicate requests, and scribble over two occupied
    shared-cache slots. ``gate.py`` hard-gates availability >= 0.99
    across chaos rounds, bounded slot recovery, zero divergence of
    non-degraded replies from the fault-free reference round (bit
    parity, not tolerance), the kill+wedge events actually landing,
    and the supervisor/router counters surfacing through the obs
    registry snapshot."""
    from repro.core import tokenizer as TOK
    from repro.core.service import CostModelService
    from repro.ir import samplers
    from repro.obs import (MetricsRegistry, register_router,
                           register_supervisor)
    from repro.serving import (FaultEvent, FaultPlan, FaultyTransport,
                               QueueTransport, ReplicaClient,
                               ReplicaSupervisor, ServiceSpec,
                               start_replicas)

    cfg = CostModelConfig(name="chaos-serve", vocab_size=512,
                          max_seq=64, embed_dim=16,
                          conv_channels=(16,) * 2, fc_dims=(32,))
    rng = np.random.default_rng(seed)
    graphs = [samplers.sample_graph(rng) for _ in range(24)]
    vocab = TOK.fit_vocab([TOK.graph_tokens(g, "ops") for g in graphs],
                          max_size=512)
    heads = CM.DEFAULT_HEADS
    svc = CostModelService(
        "conv1d", cfg,
        CM.conv_init(jax.random.PRNGKey(seed), cfg, heads=heads),
        vocab, {t: {"mu": 0.2, "sigma": 1.3} for t in heads},
        mode="ops", max_seq=64, max_batch=8)
    spec = ServiceSpec.from_service(svc)
    u = len({g.struct_key() for g in graphs})

    # The workload is the clock: round 0 spans ops [0, u) and stays
    # clean (it is the parity reference), then every seam in order.
    # The wedged slot is recovered BY the supervisor (its respawn
    # SIGKILLs the stopped process) — the late unwedge lands on the
    # already-respawned healthy slot, exercising the event kind as a
    # harmless no-op rather than racing the heartbeat detector.
    plan = FaultPlan(seed=seed, events=[
        FaultEvent(at=u, kind="corrupt",
                   key=graphs[0].struct_key()),
        FaultEvent(at=u + 1, kind="corrupt",
                   key=graphs[1].struct_key()),
        FaultEvent(at=2 * u, kind="kill", replica=0),
        FaultEvent(at=12 * u, kind="wedge", replica=1),
        FaultEvent(at=20 * u, kind="drop", replica=0, count=3),
        FaultEvent(at=20 * u + 1, kind="delay", replica=1, count=2,
                   delay_s=0.05),
        FaultEvent(at=20 * u + 2, kind="dup", replica=0, count=2),
        FaultEvent(at=40 * u, kind="unwedge", replica=1),
    ])

    tier = start_replicas(spec, 2, n_clients=1, flush_us=300.0,
                          start_timeout_s=240.0)
    reg = MetricsRegistry()
    rounds = []
    try:
        sup = ReplicaSupervisor(tier, heartbeat_s=0.25,
                                heartbeat_timeout_s=3.0,
                                restart_backoff_s=0.05,
                                start_timeout_s=240.0).start()
        try:
            handle = tier.client_handle(0)
            ft = FaultyTransport(QueueTransport(handle), plan,
                                 tier=tier)
            client = ReplicaClient(handle, transport=ft,
                                   local_cache=False, timeout_s=1.0,
                                   deadline_s=3.0, cooldown_s=0.05,
                                   oracle_fallback=True,
                                   jitter_seed=seed)
            register_supervisor(reg, sup)
            register_router(reg, client)
            ref = None

            def one_round():
                d0 = client.degraded_count
                t0 = time.perf_counter()
                try:
                    got = client.predict_all(graphs)
                    err = None
                except Exception as e:
                    got, err = None, repr(e)
                rec = {"wall_s": time.perf_counter() - t0,
                       "ok": err is None,
                       "degraded": client.degraded_count - d0,
                       "error": err}
                # parity is only claimed for rounds the tier fully
                # answered; degraded rounds carry oracle rows by design
                # and are flagged, not compared
                if got is not None and ref is not None \
                        and rec["degraded"] == 0:
                    rec["bit_equal"] = all(
                        np.array_equal(got[t], ref[t]) for t in ref)
                rounds.append(rec)
                return got

            ref = one_round()              # fault-free reference round
            if ref is None:
                raise RuntimeError("reference round failed: "
                                   f"{rounds[0]['error']}")
            n_rounds = 120 if full else 60
            stop_at = time.monotonic() + 240.0
            while time.monotonic() < stop_at:
                one_round()
                # pace the closed loop: cache-hot rounds run ~1ms, and
                # an unpaced op-clock would burn through the whole
                # schedule before the heartbeat detector (wall-clock
                # timescale) ever saw the wedge
                time.sleep(0.02)
                st = sup.stats()
                if len(rounds) >= n_rounds and plan.exhausted \
                        and st["restarts_recovered"] >= 2 \
                        and not st["respawning"]:
                    break
            # closing rounds: the tier must come all the way back —
            # non-degraded and bit-identical — once faults stop
            final_clean = False
            for _ in range(10):
                one_round()
                r = rounds[-1]
                if r["ok"] and r["degraded"] == 0 \
                        and r.get("bit_equal"):
                    final_clean = True
                    break
                time.sleep(0.5)    # residual routing cooldown drains
            st = sup.stats()
            snap = reg.snapshot()["metrics"]
            router = client.stats()
        finally:
            sup.stop()
    finally:
        tier.stop()

    avail = sum(r["ok"] for r in rounds) / len(rounds)
    nd = [r for r in rounds if r.get("bit_equal") is not None]
    diverged = sum(not r["bit_equal"] for r in nd)
    applied = {}
    for e in ft.log:
        if e["applied"]:
            applied[e["kind"]] = applied.get(e["kind"], 0) + 1
    mean_wall = float(np.mean([r["wall_s"] for r in rounds]))
    out = {
        "rounds": len(rounds),
        "availability": avail,
        "non_degraded_rounds": len(nd),
        "degraded_rounds": sum(r["degraded"] > 0 for r in rounds),
        "degraded_preds": client.degraded_count,
        "diverged": diverged,
        "final_clean": final_clean,
        "plan_exhausted": plan.exhausted,
        "faults_applied": applied,
        "kill_applied": applied.get("kill", 0) >= 1,
        "wedge_applied": applied.get("wedge", 0) >= 1,
        "restarts_total": st["restarts_total"],
        "restarts_recovered": st["restarts_recovered"],
        "recovery_s_max": st["recovery_s_max"],
        "crash_loops": st["crash_loops"],
        "inbox_resets": st["inbox_resets"],
        "tick_errors": st["tick_errors"],
        "router": {k: router[k] for k in
                   ("shed_count", "degraded_count", "deadline_expired",
                    "recv_errors", "failures", "unhealthy_now")},
        "obs_counters_present": (
            "supervisor.restarts_total" in snap
            and "router.degraded_count" in snap),
        "mean_round_s": mean_wall,
    }
    _row("chaos_serve/rounds", mean_wall * 1e6,
         f"rounds={out['rounds']};avail={avail:.3f}"
         f";degraded={out['degraded_rounds']};diverged={diverged}")
    _row("chaos_serve/recovery", st["recovery_s_max"] * 1e6,
         f"restarts={st['restarts_total']}"
         f";recovered={st['restarts_recovered']}"
         f";inbox_resets={st['inbox_resets']}"
         f";final_clean={final_clean}")
    return out


BENCHES = {
    "paper_rmse": paper_rmse,
    "operand_ablation": operand_ablation,
    "inference_speed": inference_speed,
    "kernel_bench": kernel_bench,
    "serve_bench": serve_bench,
    "serve_concurrent": serve_concurrent,
    "obs_overhead": obs_overhead,
    "opt_search": opt_search,
    "search_fleet": search_fleet,
    "search_fleet_replicated": search_fleet_replicated,
    "train_bench": train_bench,
    "transformer_extension": transformer_extension,
    "roofline_table": roofline_table,
    "ingest": ingest,
    "chaos_serve": chaos_serve,
}


def _jsonable(x):
    """Benchmark returns -> JSON: tuple keys become strings, numpy
    scalars/arrays become python numbers/lists."""
    if isinstance(x, dict):
        return {"/".join(k) if isinstance(k, tuple) else str(k):
                _jsonable(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [_jsonable(v) for v in x]
    if isinstance(x, np.ndarray):
        return x.tolist()
    if isinstance(x, (np.integer, np.floating, np.bool_)):
        return x.item()
    return x


# --------------------------------------------------------- perf trajectory
def _git_sha() -> str:
    try:
        import subprocess
        out = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True,
            cwd=os.path.dirname(os.path.abspath(__file__)))
        return out.stdout.strip() or "unknown"
    except Exception:
        return "unknown"


# Per-bench summarizers: the handful of headline scalars a trajectory
# plot wants, not the whole payload. Benches without one fall through
# to the generic ratio/speedup scrape.
_HISTORY_SUMMARY = {
    "serve_concurrent": lambda r: {
        f"speedup_c{c}": lvl["speedup_vs_serialized"]
        for c, lvl in r["levels"].items()},
    "search_fleet": lambda r: {
        "per_worker_steady_speedup": r["speedup_vs_baseline"],
        "fleet_steady_speedup": r["fleet_steady_speedup_vs_baseline"],
        "cold_speedup": r["cold_speedup_vs_baseline"],
        "bf16_spearman_min": r["bf16"]["spearman_min"]},
    "kernel_bench": lambda r: {
        "traffic_reduction": r["traffic_reduction"],
        "conv_f32_max_err": r["models"]["conv1d"]["f32_max_err"],
        "conv_bf16_spearman_min":
            r["models"]["conv1d"]["bf16_spearman_min"],
        "lstm_bf16_spearman_min":
            r["models"]["lstm"]["bf16_spearman_min"],
        "conv_wall_ratio": r["models"]["conv1d"]["wall_ratio"],
        "interpret": r["interpret"]},
    "ingest": lambda r: {
        "unk_rate_max": r["arch"]["unk_rate_max"],
        "arch_errors": r["arch"]["errors"],
        "fuzz_uncaught": r["fuzz"]["uncaught"],
        "ingest_texts_per_s": r["arch"]["texts_per_s"]},
    "search_fleet_replicated": lambda r: {
        "replicated_steady_speedup":
            r["replicated_steady_speedup_vs_baseline"],
        "replicated_cold_speedup":
            r["replicated_cold_speedup_vs_baseline"],
        "replicas": r["replicas"],
        "shed_total": r["modes"]["replicated"]["router"]["shed_total"]},
    "obs_overhead": lambda r: {
        "overhead_ratio": r["overhead_ratio"],
        "trace_completeness": r["trace"]["completeness"],
        "drift_gauges_present": r["drift_gauges_present"]},
    "chaos_serve": lambda r: {
        "availability": r["availability"],
        "diverged": r["diverged"],
        "recovery_s_max": r["recovery_s_max"],
        "restarts_recovered": r["restarts_recovered"],
        "degraded_rounds": r["degraded_rounds"]},
}


def _history_summary(name, result) -> dict:
    fn = _HISTORY_SUMMARY.get(name)
    if fn is not None:
        try:
            return _jsonable(fn(result))
        except Exception:
            pass
    if isinstance(result, dict):       # generic: headline scalars only
        return {k: _jsonable(v) for k, v in result.items()
                if isinstance(v, (int, float, np.integer, np.floating))
                and any(s in k for s in
                        ("speedup", "ratio", "rmse", "spearman"))}
    return {}


def append_history(path: str, args, summaries: dict) -> None:
    """Append one rolled-up entry (sha + per-bench headline numbers) to
    the trajectory file — the cross-PR record BENCH_*.json artifacts
    never gave us, since each run overwrote the last."""
    hist = {"entries": []}
    if os.path.exists(path):
        try:
            with open(path) as f:
                hist = json.load(f)
            hist.setdefault("entries", [])
        except Exception:
            pass                       # corrupt file: restart trajectory
    hist["entries"].append({
        "ts": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "sha": _git_sha(),
        "full": bool(args.full),
        "seed": args.seed,
        "benches": summaries})
    with open(path, "w") as f:
        json.dump(hist, f, indent=2)
        f.write("\n")
    print(f"# appended history entry -> {path} "
          f"({len(hist['entries'])} total)", flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, choices=sorted(BENCHES))
    ap.add_argument("--full", action="store_true",
                    help="paper-scale dataset/steps (slow)")
    ap.add_argument("--json-dir", default=None,
                    help="write one BENCH_<name>.json record per bench "
                         "run (CI uploads these as workflow artifacts)")
    ap.add_argument("--replicas", type=int, default=4,
                    help="replica-process count for "
                         "search_fleet_replicated")
    ap.add_argument("--history", default=None,
                    help="append a rolled-up entry (git sha + headline "
                         "numbers per bench) to this BENCH_history.json "
                         "trajectory file after the run")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()
    print("name,us_per_call,derived")
    summaries = {}
    for name, fn in BENCHES.items():
        if args.only and name != args.only:
            continue
        kw = {"full": args.full, "seed": args.seed}
        if name == "search_fleet_replicated":
            kw["replicas"] = args.replicas
        result = fn(**kw)
        summaries[name] = _history_summary(name, result)
        if args.json_dir:
            os.makedirs(args.json_dir, exist_ok=True)
            path = os.path.join(args.json_dir, f"BENCH_{name}.json")
            with open(path, "w") as f:
                json.dump({"bench": name, "full": args.full,
                           "seed": args.seed,
                           "result": _jsonable(result)}, f, indent=2)
            print(f"# wrote {path}", flush=True)
    if args.history and summaries:
        append_history(args.history, args, summaries)


if __name__ == '__main__':
    main()
