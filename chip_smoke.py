#!/usr/bin/env python3
"""Smoke test of the main path on a TPU: dataset -> TrainEngine ->
CostModelService -> CostModelServer, at the paper's published widths.

    python chip_smoke.py             # one chip: train, serve, compare
    python chip_smoke.py --chips 4   # four chips: data-parallel training

One chip (``COSTMODEL_BASE``: embed 64, 6x64 conv channels, max_seq 256,
the three default heads):

* builds its dataset from ``--seed`` (no file is read from disk);
* trains ``conv1d`` and ``lstm`` for TRAIN_STEPS TrainEngine steps each,
  and checks the steps taken, that the loss is finite and that it falls;
* for each kind serves a few hundred requests through CostModelServer
  (warmed up) on the XLA apply and on the Pallas kernel, in f32 and in
  bf16, with zero errors and zero sheds allowed;
* checks that each kernel program holds ``tpu_custom_call`` (the Mosaic
  kernel compiled; no interpret mode, no reference in its place);
* compares, per head, with Spearman >= 0.99 and an absolute tolerance
  (TOLERANCES, CPU_ATOL), every row forwarded in a batch of MAX_BATCH:
  the kernel path against the XLA path on the chip, and the chip's f32
  XLA forward at the highest matmul precision against a CPU reference
  of the same params run in this process (the default-precision figure
  is printed beside it).

Four chips (``--chips 4``): ``COSTMODEL_100M`` conv1d trained for a few
steps with ``mesh_data=4`` against the same seed and batches with
``mesh_data=1``; prints the per-device shard shapes of the params and
of a batch, and checks that the losses agree (DP_LOSS_RTOL). No other
phase runs.

The lines before the last are informational (host-clock times include
compilation; they are not device metrics). The last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Any failed phase, or a JAX that finds no TPU, exits non-zero without
that line: there is no CPU fallback.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

N_GRAPHS = 1500         # dataset graphs (before augmentation)
TRAIN_STEPS = 48        # TrainEngine steps per kind (one chip)
DP_STEPS = 6            # TrainEngine steps per mesh (--chips 4)
MAX_BATCH = 16          # server max_batch: 8 ladder sizes x 4 buckets
N_REQUESTS = 288        # per service; spans every serving bucket
CONCURRENCY = 8         # closed-loop client threads
SPEARMAN_MIN = 0.99
# Absolute tolerances on normalized predictions (z-scores of log1p
# targets, so 0.01 is a hundredth of a target standard deviation).
# Every compared row is forwarded in a batch of exactly MAX_BATCH
# (fixed_batches), so each call compares the same programs.
#
# Kernel vs XLA on the chip, keyed by (kind, dtype). f32: both run f32
# matmuls at the TPU's default precision, one bf16 pass per product
# (8-bit mantissa), and round in different orders: up to 3.2e-3 apart
# (conv) and 0 (lstm) on a v5e. bf16: the repo's bf16 contract is rank
# order (Spearman >= 0.99, as in its drift gates); the bound catches
# gross errors at ten times the 9.1e-3 read on a v5e. The XLA bf16
# LSTM keeps its (h, c) carry in bf16 while the kernel carries f32.
TOLERANCES = {("conv1d", "f32"): 1e-2, ("lstm", "f32"): 1e-2,
              ("conv1d", "bf16"): 0.1, ("lstm", "bf16"): 0.1}
# Chip vs CPU: the chip's f32 XLA forward under
# jax.default_matmul_precision("highest") against the CPU's, so both
# compute in f32 and only summation order and transcendental ulps
# differ: 4.8e-7 (conv) and 2.6e-4 (lstm, 256 recurrent steps) on a
# v5e. The bound sits below the 3.8e-3 (conv) and 8.1e-2 (lstm) that
# the default one-pass precision gives against the CPU, so a forward
# that lost f32 precision fails it; that default-precision figure is
# printed with its Spearman gated, not bounded.
CPU_ATOL = 2e-3
# --chips 4: both runs see the same init and batches; only the
# summation order of the all-reduced gradients and of the loss differs
# (f32 rounding, ~1e-7 per step), and Adam's normalization can amplify
# that on near-zero gradients over a few steps: 1.5e-7 at step 1 and
# 2.3e-4 by step 5 on four v5e chips.
DP_LOSS_RTOL = 1e-3


class PhaseError(RuntimeError):
    pass


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise PhaseError(msg)


def log(msg: str) -> None:
    print(msg, flush=True)


def request_graphs(seed: int):
    """Search-candidate traffic: sampled graphs plus 2x and 4x unrolled
    variants, so token lengths span every bucket up to max_seq."""
    import numpy as np
    from repro.ir import samplers
    from repro.opt.rewrites import unroll_graph
    rng = np.random.default_rng(seed + 1)
    base = [samplers.sample_graph(rng) for _ in range(N_REQUESTS // 3)]
    return (base + [unroll_graph(g, 2) for g in base]
            + [unroll_graph(g, 4) for g in base])


# ------------------------------------------------------------ one chip
def train_phase(kind, cfg, train, steps, seed):
    import numpy as np
    from repro.core import models as CM
    from repro.core import trainer as TR
    dts = []
    engine = TR.TrainEngine(kind, cfg, CM.DEFAULT_HEADS, steps=steps,
                            batch_size=64, lr=2e-3, seed=seed, log_every=1)
    res = engine.fit(train, on_step=lambda step, dt: dts.append(dt))
    losses = np.asarray([loss for _, loss in res.history])
    check(int(res.stats["steps"]) == steps,
          f"{kind}: took {res.stats['steps']:.0f} steps, asked {steps}")
    check(len(losses) == steps and np.isfinite(losses).all(),
          f"{kind}: non-finite or missing losses {losses}")
    k = max(steps // 8, 1)
    first, last = float(losses[:k].mean()), float(losses[-k:].mean())
    check(last < first, f"{kind}: loss did not fall ({first} -> {last})")
    log(f"train {kind}: steps {int(res.stats['steps'])}/{steps} "
        f"loss(mean of {k}) {first:.4f} -> {last:.4f}; "
        f"{res.stats['steps_per_s']:.2f} steps/s incl. compile, "
        f"median step {np.median(dts) * 1e3:.2f} ms (host clock)")
    return res


def fixed_batches(entries):
    """(row indices, (MAX_BATCH, bucket) ids) covering the bucket-padded
    id rows ``entries``: grouped per bucket, zero-padded with all-PAD
    rows to MAX_BATCH, so every row runs in a program of one shape."""
    import numpy as np
    for width in sorted({len(e) for e in entries}):
        idx = [i for i, e in enumerate(entries) if len(e) == width]
        for lo in range(0, len(idx), MAX_BATCH):
            part = idx[lo:lo + MAX_BATCH]
            ids = np.zeros((MAX_BATCH, width), np.int32)
            ids[:len(part)] = np.stack([entries[i] for i in part])
            yield part, ids


def batch_rows(forward, entries, n_heads):
    """``forward((MAX_BATCH, bucket) ids) -> (MAX_BATCH, n_heads)`` over
    ``fixed_batches(entries)`` -> (N, n_heads) rows."""
    import numpy as np
    out = np.zeros((len(entries), n_heads), np.float32)
    for part, ids in fixed_batches(entries):
        out[part] = np.asarray(forward(ids))[:len(part)]
    return out


def serve_one(kind, cfg, res, vocab, graphs, dtype, use_kernel):
    """Serve ``graphs`` through a warmed CostModelServer; return the
    service and its rows (N, n_heads) forwarded in batches of
    MAX_BATCH. Logs how far those are from the served rows, which the
    server batched by closed-loop timing."""
    import numpy as np
    from repro.core.server import CostModelServer
    from repro.core.service import CostModelService
    from repro.launch.serve import run_clients
    svc = CostModelService(kind, cfg, res.params, vocab, res.norm_stats,
                           mode="ops", max_seq=cfg.max_seq,
                           max_batch=MAX_BATCH, dtype=dtype,
                           use_kernel=use_kernel)
    server = CostModelServer(svc, max_batch=MAX_BATCH)
    t0 = time.perf_counter()
    server.start(warmup=True)
    warm_s = time.perf_counter() - t0
    try:
        wall = run_clients(server, graphs, CONCURRENCY)
        snap = server.metrics_snapshot()
    finally:
        server.stop()
    path = "kernel" if use_kernel else "xla"
    check(snap["requests"] == len(graphs) and snap["shed"] == 0,
          f"{kind}/{path}/{dtype}: {snap['requests']} requests, "
          f"{snap['shed']} shed")
    if use_kernel:
        text = svc.compiled_forward(MAX_BATCH, svc.buckets[-1]).as_text()
        check("tpu_custom_call" in text,
              f"{kind}/{dtype}: no tpu_custom_call in the kernel program")
    entries = [svc.entry(g) for g in graphs]
    served = svc.predict_entries(entries)               # LRU hits
    fixed = batch_rows(
        lambda ids: svc.forward_collect(svc.forward_dispatch(ids)),
        [ids for _, ids in entries], len(svc.heads))
    log(f"serve {kind}/{path}/{dtype}: warmup {warm_s:.2f} s for "
        f"{len(svc.buckets) * len(svc.batch_ladder)} programs "
        f"(buckets {svc.buckets}), {snap['requests']} requests answered "
        f"in {wall:.2f} s, 0 errors, {snap['shed']} shed, "
        f"p99 {snap['latency_p99_us']:.0f} us, "
        f"truncations {svc.cache_stats()['truncations']}"
        + (", tpu_custom_call present" if use_kernel else "")
        + f" (host clock); served vs batch-{MAX_BATCH} rows max|diff| "
        f"{float(np.abs(served - fixed).max()):.2e}")
    return svc, fixed


def xla_forward(kind, params, svc, graphs, device):
    """The XLA apply of ``params`` on ``device``, at the matmul precision
    in force, over ``graphs`` in batches of MAX_BATCH -> (N, n_heads)."""
    import jax
    import numpy as np
    from repro.core import models as CM
    apply_fn = jax.jit(CM.get_model(kind)[1])
    p_dev = jax.device_put(params, device)

    def forward(ids):
        pred = apply_fn(p_dev, jax.device_put(ids, device))
        return np.stack([np.asarray(pred[t]) for t in svc.heads], 1)
    return batch_rows(forward, [svc.entry(g)[1] for g in graphs],
                      len(svc.heads))


def compare(label, heads, got, want, atol):
    """Gate per-head Spearman >= SPEARMAN_MIN and, unless ``atol`` is
    None (the figure is printed only), max |got - want| <= atol."""
    import numpy as np
    from repro.opt.evaluate import spearman
    rhos = [spearman(want[:, i], got[:, i]) for i in range(len(heads))]
    err = np.abs(got - want).max(axis=0)
    log(f"compare {label}: spearman "
        + " ".join(f"{t}={r:.5f}" for t, r in zip(heads, rhos))
        + "; max|diff| "
        + " ".join(f"{t}={e:.2e}" for t, e in zip(heads, err))
        + (f" (atol {atol})" if atol is not None else " (not bounded)"))
    check(min(rhos) >= SPEARMAN_MIN
          and (atol is None or float(err.max()) <= atol),
          f"{label}: spearman {rhos} / max abs diff {err} out of bounds")


def dataset(cfg, seed):
    """(dataset, train split) built from ``seed``; nothing read from disk."""
    from repro.ir import dataset as DS
    ds = DS.build_dataset(N_GRAPHS, mode="ops", max_seq=cfg.max_seq,
                          vocab_size=cfg.vocab_size, augment_factor=2,
                          seed=seed, layout="bucketed")
    return ds, ds.split(0.1, seed=seed)[0]


def one_chip(seed):
    import jax
    from repro.configs.costmodel import COSTMODEL_BASE as cfg
    from repro.kernels.ops import KERNEL_KINDS
    t0 = time.perf_counter()
    ds, train = dataset(cfg, seed)
    graphs = request_graphs(seed)
    log(f"dataset: {len(train)} train rows, vocab {ds.vocab.size}, "
        f"{len(graphs)} request graphs, built in "
        f"{time.perf_counter() - t0:.1f} s from seed {seed}")
    for kind in KERNEL_KINDS:
        res = train_phase(kind, cfg, train, TRAIN_STEPS, seed)
        for dtype in ("f32", "bf16"):
            svc, xla = serve_one(kind, cfg, res, ds.vocab, graphs, dtype,
                                 use_kernel=False)
            _, kern = serve_one(kind, cfg, res, ds.vocab, graphs, dtype,
                                use_kernel=True)
            compare(f"{kind}/{dtype} kernel vs xla (chip)", svc.heads,
                    kern, xla, TOLERANCES[kind, dtype])
            if dtype == "f32":
                ref = xla_forward(kind, res.params, svc, graphs,
                                  jax.devices("cpu")[0])
                with jax.default_matmul_precision("highest"):
                    hi = xla_forward(kind, res.params, svc, graphs,
                                     jax.devices()[0])
                compare(f"{kind}/f32 xla chip (highest precision) vs cpu",
                        svc.heads, hi, ref, CPU_ATOL)
                compare(f"{kind}/f32 xla chip (default precision) vs cpu",
                        svc.heads, xla, ref, None)
    return len(jax.devices())


# ---------------------------------------------------------- four chips
def four_chips(seed):
    import jax
    import numpy as np
    from repro.configs.costmodel import COSTMODEL_100M as cfg
    from repro.core import models as CM
    from repro.core import trainer as TR
    from repro.ir import dataset as DS
    check(len(jax.devices()) >= 4,
          f"--chips 4 needs 4 devices, JAX found {len(jax.devices())}")
    _, train = dataset(cfg, seed)
    losses = {}
    for md in (4, 1):
        engine = TR.TrainEngine("conv1d", cfg, CM.DEFAULT_HEADS,
                                steps=DP_STEPS, batch_size=64,
                                lr=1e-3, seed=seed, log_every=1,
                                mesh_data=md)
        res = engine.fit(train)
        losses[md] = np.asarray([loss for _, loss in res.history])
        check(int(res.stats["steps"]) == DP_STEPS
              and np.isfinite(losses[md]).all(),
              f"mesh_data={md}: steps {res.stats['steps']}, "
              f"losses {losses[md]}")
        log(f"dp mesh_data={md}: {DP_STEPS} steps, "
            f"{res.stats['steps_per_s']:.2f} steps/s incl. compile "
            f"(host clock); losses {losses[md].tolist()}")
        if md == 4:
            p = res.params
            for name, leaf in (("emb", p["emb"]),
                               ("convs[0].w", p["convs"][0]["w"]),
                               ("fc[0].w", p["fc"][0]["w"])):
                shards = [tuple(s.data.shape)
                          for s in leaf.addressable_shards]
                log(f"dp param {name} {leaf.shape}: per-device shards "
                    f"{shards}")
            y, _ = DS.stacked_normalized_targets(train.targets,
                                                 engine.heads)
            batch = engine.place_batch(
                next(iter(engine.make_loader(train, y))))
            for k, v in batch.items():
                # a batch split into width-sorted chunks: each chunk
                for a in getattr(v, "parts", (v,)):
                    shards = [(str(s.device), tuple(s.data.shape))
                              for s in a.addressable_shards]
                    log(f"dp batch {k} {a.shape} spec {a.sharding.spec}: "
                        f"{shards}")
                    check(a.sharding.spec[0] == "data"
                          and all(s[1][0] == a.shape[0] // 4
                                  for s in shards),
                          f"batch {k} is not split along data: {shards}")
    rel = np.abs(losses[4] - losses[1]) / np.abs(losses[1])
    log(f"dp losses 4 vs 1 device: max rel diff {rel.max():.3e} "
        f"(rtol {DP_LOSS_RTOL})")
    check(float(rel.max()) <= DP_LOSS_RTOL,
          f"4-chip losses differ from 1-chip: {rel.tolist()}")
    return len(jax.devices())


def count_cache_events():
    """Counts of this process's persistent compile-cache events, from
    JAX's monitoring hooks: lookups, hits, and entries written (JAX
    names a write ``cache_misses``; a miss that compiled in under
    ``jax_persistent_cache_min_compile_time_secs`` writes nothing).
    JAX's own ``compile_time_saved_sec`` is left out: it stores compile
    times truncated to whole seconds, so it cannot say what a hit
    saved."""
    from jax import monitoring
    counts = {"compile_requests_use_cache": 0, "cache_hits": 0,
              "cache_misses": 0}

    def on_event(event, **_):
        name = event.rsplit("/", 1)[-1]
        if event.startswith("/jax/compilation_cache/") and name in counts:
            counts[name] += 1

    monitoring.register_event_listener(on_event)
    return counts


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    platforms = os.environ.get("JAX_PLATFORMS")
    if platforms and "cpu" not in platforms.split(","):
        # the chip-vs-CPU comparison needs the CPU backend beside the chip
        os.environ["JAX_PLATFORMS"] = platforms + ",cpu"
    try:
        import jax
        from repro.runtime.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: cannot import the program ({e}); run it from "
              f"a checkout of the repo", file=sys.stderr)
        return 2
    try:
        dev = jax.devices()[0]
    except RuntimeError as e:
        print(f"chip_smoke: JAX found no device ({e})", file=sys.stderr)
        return 2
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (device 0 is "
              f"{dev.platform!r}); this smoke runs on the chip only",
              file=sys.stderr)
        return 2
    cache_dir = enable_compile_cache()
    cache = count_cache_events()
    log(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}")
    try:
        run = four_chips if args.chips == 4 else one_chip
        count = run(args.seed)
    except Exception:
        traceback.print_exc()
        print("chip_smoke: FAILED", file=sys.stderr)
        return 1
    log(f"compile cache {cache_dir}: {cache['compile_requests_use_cache']}"
        f" lookups, {cache['cache_hits']} hits, "
        f"{cache['cache_misses']} entries written")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": count}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
