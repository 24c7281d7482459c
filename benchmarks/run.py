"""Accuracy tables and correctness gates, run on any backend.

Prints ``name,us_per_call,derived`` CSV rows (per the repo convention).
Speed is measured on the chip by ``benchmarks/chip/run.py``; nothing
here is a speed benchmark.

  paper_rmse            — §4: RMSE of FC / LSTM / Conv1D on register
                          pressure and vALU utilization (ops-only tokens).
  operand_ablation      — Fig 6: ops-only vs ops+operands Conv1D
                          accuracy, %-exact for register pressure.
  opt_search            — repro.opt beam search over rewrite sequences
                          through the server vs the one-shot
                          FusionAdvisor baseline, judged by the analyzer
                          oracle (gated by gate.py::gate_opt_search).
  transformer_extension — beyond-paper: a Transformer cost model
                          head-to-head with the Conv1D model.
  ingest                — the real-MLIR front door: arch-corpus ingestion
                          plus a fuzz corpus (gated by gate_ingest: no
                          uncaught exception, no error, no bare <unk>).
  chaos_serve           — supervised 2-replica tier under a seeded
                          scripted fault plan (kill, wedge, drop/delay/
                          dup, torn shared-cache slot): availability,
                          recovery time, bit-parity of non-degraded
                          replies, and obs counter presence (gated by
                          gate_chaos_serve).

``--full`` uses paper-scale settings (20k+ graphs); default is CI-scale.
``--json-dir DIR`` additionally writes one ``BENCH_<name>.json`` record
per bench (the CI bench-smoke job uploads these as artifacts, and
``benchmarks/gate.py`` checks them).
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import numpy as np

from repro.configs.costmodel import CostModelConfig
from repro.core import models as CM
from repro.core import trainer as TR
from repro.ir import dataset as DS
from repro.runtime.compile_cache import enable_compile_cache


def _row(name, us, derived):
    print(f"{name},{us:.2f},{derived}", flush=True)


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
    "opt_search": opt_search,
    "transformer_extension": transformer_extension,
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


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, choices=sorted(BENCHES))
    ap.add_argument("--full", action="store_true",
                    help="paper-scale dataset/steps (slow)")
    ap.add_argument("--json-dir", default=None,
                    help="write one BENCH_<name>.json record per bench "
                         "run (CI uploads these as workflow artifacts)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()
    print("name,us_per_call,derived")
    for name, fn in BENCHES.items():
        if args.only and name != args.only:
            continue
        result = fn(full=args.full, seed=args.seed)
        if args.json_dir:
            os.makedirs(args.json_dir, exist_ok=True)
            path = os.path.join(args.json_dir, f"BENCH_{name}.json")
            with open(path, "w") as f:
                json.dump({"bench": name, "full": args.full,
                           "seed": args.seed,
                           "result": _jsonable(result)}, f, indent=2)
            print(f"# wrote {path}", flush=True)


if __name__ == '__main__':
    main()
