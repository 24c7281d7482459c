"""Closed-loop autotuner fleet over a real model's layer programs: each
search starts from a program's StableHLO text, which the program's front
door parses inside the window, as an autotuner receives a module from
its compiler; then ``beam_search`` costs rewrite candidates through the
gateway, as in ``fleet.py``, whose machinery this reuses.

Traffic parameters (``traffic/<name>.json``), beyond ``fleet.py``'s
``workers``, ``beam_width``, ``max_steps``, ``eval_budget``,
``order_block``, ``repeats``, ``stagger``, ``max_batch``, ``flush_us``,
``keep`` and ``check_rows``, and the service's ``batch_ladder``: the
pool is every program of
``jamba_reference.grid(layers, kinds, batches, lengths)`` (the layer,
the program kind, the batch, the sequence or cache length), lowered in
set-up from the benchmark's own reference at the configuration's
published widths (``architecture``), so the traffic does not move with
the program; the warm-up searches take the programs of
``warm_batches`` x ``warm_lengths``. ``layer_check`` gives the prefill
(batch, length) and the decode step's cache length of the check of the
program's own layers, and ``limits`` the numbers compared.

The configuration holds the served cost model under ``cost_model``
(the keys of ``costmodel-100m.json``) and the architecture's
config.json under ``architecture``; its top-level ``num_experts`` is
the share of each MoE layer's experts one chip holds in the check.

``correct`` compares three things, after the window:

* ``row_gap_mean``: a seeded sample of the rows served in the window,
  and the longest programs' rows, against the reference cost model in
  the form of one convolution a layer (``conv_rows``), over tokens made
  by this module's ``tokens`` (the paper's layout with a loop's trip
  count after it). It also reads the gap from ``reference.py``'s sum of
  shifted products (``row_gap_mean_shifted``), which rounds otherwise
  on a TPU;
* ``frontdoor_lossy_ops``: over the whole pool, the ops of each text
  missing from, or renamed in, the graph the front door parsed, plus
  loops without their trip count (``jamba_reference.lossy_ops``);
* ``layer_gap``: with the cost model dropped, the program's attention,
  Mamba, MoE (the held share) and FFN layers on seeded random weights at
  the published widths, against ``jamba_reference`` in float32 computed
  in blocks: a prefill and one decode step through the cache (the MoE
  and FFN, which read no cache, over ``DECODE_ROWS`` one-token rows); the
  largest relative gap. In an MoE layer the tokens whose top-2 experts
  lie within the bfloat16 router's rounding of the third
  (``jamba_reference.routing_ties``) are left out of the comparison, and
  their share is read (``moe_tie_share``): either choice of experts is
  right at the configuration's precision, and one such token moves the
  gap by more than all the rounding of the others. Every run also reads
  the same gaps with the reference's inputs rounded to float8 in the
  program's place (``layer_gap_float8``) and with the Mamba D term
  dropped (``layer_gap_no_d``), which the limit must fail.

The limits in ``fleet8_jamba2.json`` sit between what the program and
the controls read at the published widths on a TPU v5e:
``row_gap_mean`` 0.012 (the program 0.0009-0.0042, the cost model in
bfloat16 0.035-0.043), ``layer_gap`` 0.02 (the program at most 0.0054,
float8 at least 0.086 in every kind of layer, no D term at least 0.81),
and ``frontdoor_lossy_ops`` 0: a lossless front door loses no op.
"""
from __future__ import annotations

import dataclasses
import gc
import threading
from typing import Dict, List

import numpy as np

from chip import harness as H
from chip import jamba_reference as JR
from chip import reference as REF
from chip import serving as SV
from chip.drivers import fleet as FL

ARCH = "jamba2-mini"
LOOP = "while"


def system_arch(cfg: dict):
    """The program's ArchConfig for the architecture, at the widths of
    ``cfg["architecture"]``; a program that lacks the architecture
    cannot run the cell."""
    try:
        from repro.configs import ARCHS
        base = ARCHS[ARCH]
        base.moe.is_moe(0)
    except (ImportError, KeyError, AttributeError) as e:
        raise H.BenchError(f"the program has no {ARCH} architecture "
                           f"({type(e).__name__}: {e})") from e
    a = cfg["architecture"]
    c = JR.widths(a)
    return dataclasses.replace(
        base, d_model=c["d_model"], n_heads=c["n_heads"],
        n_kv_heads=c["n_kv_heads"], head_dim=c["head_dim"],
        d_ff=c["d_ff"], norm_eps=c["eps"], vocab=a["vocab_size"],
        moe=dataclasses.replace(
            base.moe, n_experts=c["n_experts"], top_k=c["top_k"],
            d_ff_expert=c["d_ff"], moe_every=c["expert_period"],
            moe_offset=c["expert_offset"]),
        hybrid=dataclasses.replace(
            base.hybrid, period=c["period"], attn_index=c["attn_offset"],
            d_state=c["d_state"], d_conv=c["d_conv"], expand=c["expand"],
            dt_rank=c["dt_rank"]))


def trips_token(trips) -> str:
    return "<trips=?>" if trips is None else f"<trips={int(trips)}>"


def tokens(g, mode: str) -> List[str]:
    """``reference.tokens`` with a loop's trip count as one token after
    its opcode."""
    out = [REF.BOS] + [REF.shape_token(g.values[i])
                       for i in range(g.n_args)]
    out.append(REF.SEP)
    for op in g.ops:
        if mode == "ops_operands":
            out.append(REF._ssa(g, op.result))
        out.append(f"xpu.{op.opcode}")
        if op.opcode == LOOP:
            out.append(trips_token(op.attrs.get("trips")))
        if mode == "ops_operands":
            out.extend(REF._ssa(g, o) for o in op.operands)
        out.append(REF.shape_token(g.values[op.result]))
    out.append(REF.SEP)
    out += [REF.shape_token(g.values[o]) for o in g.outputs]
    out.append(REF.EOS)
    return out


def vocab(cm: dict, graphs) -> Dict[str, int]:
    """Ids fitted on the pool's own programs, with the fused-op opcode
    and the bfloat16 spelling of every float32 shape (what rewrites
    emit) reserved first."""
    seqs = [tokens(g, cm["mode"]) for g in graphs]
    shapes = sorted({t for s in seqs for t in s if t.endswith("xf32")})
    extra = ["xpu.fused"] + [t[:-3] + "bf16" for t in shapes]
    return REF.build_vocab(seqs, cm["vocab_size"], extra)


def lower_pool(c: dict, entries) -> List[str]:
    return [JR.lower(layer, kind, b, n, c) for layer, kind, b, n in entries]


def service(cm: dict, params, vocab_ids: Dict[str, int], tr: dict):
    """``serving.service`` at the gateway's ``max_batch`` with the
    traffic's ``batch_ladder``: each (ladder batch, bucket) forward of
    the 100M model compiles in seconds, so the ladder is coarse."""
    from repro.core import tokenizer as TOK
    from repro.core.service import CostModelService
    return CostModelService(
        cm["kind"], SV.model_config(cm), params,
        TOK.Vocab(dict(vocab_ids)), SV.norm_stats(cm), mode=cm["mode"],
        max_seq=cm["max_seq"], max_batch=tr["max_batch"],
        batch_ladder=tuple(tr["batch_ladder"]))


# ------------------------------------------------------------ row check
def conv_rows(p, ids: np.ndarray, heads, precision: str, *,
              dtype=None, block: int = 256) -> np.ndarray:
    """``reference.forward_rows`` with each convolution as one
    ``lax.conv_general_dilated`` over the window, not a sum of shifted
    products: the same function, rounded as a convolution is. On a TPU
    at default precision the two forms of the 100M model round apart
    (0.020-0.022 in ``row_gaps`` units over served rows), while the
    program's own full-width forward equals this form exactly, so the
    served rows are compared with this form. ``dtype`` bfloat16 is the
    control."""
    import jax
    import jax.numpy as jnp
    dtype = dtype or jnp.float32

    def fwd(pp, x):
        pp = jax.tree.map(lambda a: a.astype(dtype), pp)
        h = pp["emb"][x] * (x != 0).astype(dtype)[..., None]
        for layer in pp["convs"]:
            fs = layer["w"].shape[0]
            h = jax.nn.relu(jax.lax.conv_general_dilated(
                h, layer["w"], (1,), [((fs - 1) // 2, fs // 2)],
                dimension_numbers=("NWC", "WIO", "NWC"),
                precision=precision) + layer["b"])
        h = h.max(axis=1)
        for layer in pp["fc"]:
            h = jax.nn.relu(jnp.matmul(h, layer["w"], precision=precision)
                            + layer["b"])
        return jnp.stack([jnp.matmul(h, pp["heads"][t]["w"],
                                     precision=precision)[:, 0]
                          + pp["heads"][t]["b"][0] for t in heads],
                         axis=1).astype(jnp.float32)
    fn = jax.jit(fwd)
    out = []
    for i in range(0, len(ids), block):
        part = ids[i:i + block]
        n = len(part)
        part = np.concatenate(
            [part, np.zeros((block - n, part.shape[1]), part.dtype)])
        out.append(np.asarray(fn(p, part))[:n])
    return np.concatenate(out)


# ----------------------------------------------------------- layer check
def _layer_fns(scfg, cdt):
    """The program's layers, jitted: a prefill, and a decode step at the
    last position after a prefill of the others (the cache it writes,
    the state it hands over)."""
    import jax
    import jax.numpy as jnp
    from repro.models import layers as L
    from repro.models import mamba as MB
    from repro.models import moe as M

    def pos(x, start=0):
        return jnp.broadcast_to(start + jnp.arange(x.shape[1]), x.shape[:2])

    prefill = {
        "attention": lambda p, x: L.attention_apply(
            p, x, scfg, positions=pos(x), cdt=cdt)[0],
        "mamba": lambda p, x: MB.mamba_apply(p, x, scfg, cdt=cdt)[0],
        "moe": lambda p, x: M.moe_apply(p, x, scfg, cdt=cdt)[0],
        "ffn": lambda p, x: L.ffn_apply(p, x, cdt=cdt)}

    def attention_decode(p, x):
        n = x.shape[1] - 1
        kv = L.attention_kv(p, x[:, :n], scfg, positions=pos(x[:, :n]),
                            cdt=cdt)
        cache = {k: jnp.pad(v, ((0, 0), (0, 0), (0, 1), (0, 0)))
                 for k, v in kv.items()}
        return L.attention_apply(p, x[:, n:], scfg, positions=pos(
            x[:, n:], n), cdt=cdt, cache=cache, cache_index=jnp.int32(n))[0]

    def mamba_decode(p, x):
        n = x.shape[1] - 1
        _, st = MB.mamba_apply(p, x[:, :n], scfg, cdt=cdt,
                               return_state=True)
        return MB.mamba_apply(p, x[:, n:], scfg, cdt=cdt, state=st)[0]

    decode = {"attention": attention_decode, "mamba": mamba_decode,
              "moe": lambda p, x: prefill["moe"](p, decode_rows(x)),
              "ffn": lambda p, x: prefill["ffn"](p, decode_rows(x))}
    return ({k: jax.jit(f) for k, f in prefill.items()},
            {k: jax.jit(f) for k, f in decode.items()})


DECODE_ROWS = 8


def decode_rows(x):
    """The input of an MoE or FFN decode step, which reads no cache:
    the last ``DECODE_ROWS`` positions of ``x`` as as many one-token
    rows (one token alone falls wholly outside the held experts about
    one time in four)."""
    return x[0, -DECODE_ROWS:, None, :]


_JITTED: Dict[tuple, object] = {}


def _jit(name: str, c: dict, fn):
    """One jitted function per (name, widths) for the run's lifetime."""
    key = (name, tuple(sorted(c.items())))
    if key not in _JITTED:
        import jax
        _JITTED[key] = jax.jit(fn)
    return _JITTED[key]


def _reference(kind: str, p, x, c: dict, block: int, decode: bool):
    """The reference layer in float32 at 'highest', computed in blocks
    that fit: attention by blocks of ``block`` query rows (decode: the
    last row), the MoE expert by expert (the shares add up), the others
    whole; an MoE or FFN decode step over ``decode_rows``."""
    import jax
    import jax.numpy as jnp
    x = jnp.asarray(x, jnp.float32)
    S = x.shape[1]
    with jax.default_matmul_precision("highest"):
        if kind == "attention":
            if decode:
                return _jit("attention.last", c, lambda pp, xx: JR.attention(
                    pp, xx, c, (xx.shape[1] - 1, 1)))(p, x)
            def rows(n):
                return _jit(f"attention.{n}", c, lambda pp, xx, lo:
                            JR.attention(pp, xx, c, (lo, n)))
            return jnp.concatenate([
                rows(min(block, S - lo))(p, x, jnp.int32(lo))
                for lo in range(0, S, block)], axis=1)
        if decode and kind != "mamba":
            x = decode_rows(x)
        if kind == "moe":
            def expert(pp, xx, e):
                """Expert ``e``'s part, its weights sliced inside the
                program (no copy of them outlives it)."""
                share = {k: (v if k == "router" else
                             jax.lax.dynamic_slice_in_dim(v, e, 1))
                         for k, v in pp.items()}
                return JR.moe(share, xx, c, first_expert=e)
            one = _jit("moe", c, expert)
            out = 0.0
            for e in range(p["w_gate"].shape[0]):
                out = out + one(p, x, jnp.int32(e))
            return out
        out = _jit(kind, c, lambda pp, xx: JR.LAYERS[kind](pp, xx, c))(p, x)
        return out[:, -1:] if decode else out


def _float8(tree):
    """Every leaf rounded to float8 and kept in its own dtype (each
    float8 value is a bfloat16 value)."""
    import jax
    rnd = _jit("float8", {}, lambda t: jax.tree.map(
        lambda a: JR.round_float8(a).astype(a.dtype), t))
    return rnd(tree)


def layer_readings(cfg: dict, tr: dict, seed: int,
                   control: bool = False) -> Dict[str, float]:
    """Relative gaps of the program's layers from the reference, each
    kind of layer at prefill and one decode step; with ``control`` the
    float8-rounded reference stands in for the program."""
    import jax
    import jax.numpy as jnp
    scfg = system_arch(cfg)
    c = JR.widths(cfg["architecture"])
    held = int(cfg["num_experts"])
    chk = tr["layer_check"]
    (b, n), cache = chk["prefill"], int(chk["decode_cache"])
    block = int(chk.get("block", 512))
    prefill, decode = _layer_fns(scfg, jnp.bfloat16)
    key = jax.random.fold_in(SV.key(seed), 17)
    out: Dict[str, float] = {}
    for i, kind in enumerate(JR.LAYERS):
        kp, kx, kd = jax.random.split(jax.random.fold_in(key, i), 3)
        p = JR.init(kp, kind, c, n_experts=held)
        for phase, x in (("prefill", jax.random.normal(
                              kx, (b, n, c["d_model"]), jnp.bfloat16)),
                         ("decode", jax.random.normal(
                             kd, (1, cache, c["d_model"]), jnp.bfloat16))):
            dec = phase == "decode"
            want = np.asarray(_reference(kind, p, x, c, block, dec))
            keep = np.ones(want.shape[:2], bool)
            if kind == "moe":
                # a token whose top-2 the bfloat16 router may decide
                # otherwise is served by other experts, rightly: compare
                # the tokens whose routing the precision cannot change
                ties = np.asarray(_jit("ties", c, lambda pp, xx: (
                    JR.routing_ties(pp, xx, c)))(
                        p, jnp.asarray(decode_rows(x) if dec else x,
                                       jnp.float32)))
                keep = ~ties
                out[f"moe_tie_share.{phase}"] = float(ties.mean())

            def gap(y):
                return JR.rel_gap(np.asarray(y)[keep], want[keep])
            if control:
                got = _reference(kind, _float8(p), _float8(x), c, block, dec)
            else:
                got = (decode if dec else prefill)[kind](p, x)
            out[f"layer_gap.{kind}.{phase}"] = gap(got)
            f8 = _reference(kind, _float8(p), _float8(x), c, block, dec)
            out[f"layer_gap_float8.{kind}.{phase}"] = gap(f8)
            if kind == "mamba":
                no_d = (decode if dec else prefill)[kind](
                    dict(p, D=jnp.zeros_like(p["D"])), x)
                out[f"layer_gap_no_d.{phase}"] = gap(no_d)
            del want, got, f8
            gc.collect()
        del p
    out["layer_gap"] = max(v for k, v in out.items()
                           if k.startswith("layer_gap."))
    out["layer_gap_float8"] = max(v for k, v in out.items()
                                  if k.startswith("layer_gap_float8."))
    out["layer_gap_no_d"] = max(v for k, v in out.items()
                                if k.startswith("layer_gap_no_d."))
    return out


# ------------------------------------------------------------------- run
def run(cell, *, seed, seconds, trace_dir, t_process, compiles, hooks):
    import jax
    import jax.numpy as jnp
    from repro.core.server import CostModelServer
    from repro.ir import frontdoor as FD
    from repro.opt.search import beam_search

    cfg, tr = cell.config, cell.traffic
    system_arch(cfg)
    cm = cfg["cost_model"]
    c = JR.widths(cfg["architecture"])
    marks = [("start", H.now())]
    entries = JR.grid(tr["layers"], tr["kinds"], tr["batches"],
                      tr["lengths"])
    texts = lower_pool(c, entries)
    warm_texts = lower_pool(c, JR.grid(tr["layers"], tr["kinds"],
                                       tr["warm_batches"],
                                       tr["warm_lengths"]))
    marks.append(("lowering", H.now()))
    parsed = [FD.ingest(t) for t in texts + warm_texts]
    bad = [i for i, r in enumerate(parsed) if getattr(r, "graph", None)
           is None]
    if bad:
        raise RuntimeError(f"the front door parsed no graph from "
                           f"{len(bad)} of the pool's programs")
    graphs = [r.graph for r in parsed]
    vocab_ids = vocab(cm, graphs)
    marks.append(("parse and vocabulary", H.now()))
    params = SV.weights(cm, seed)
    marks.append(("weights", H.now()))
    svc = service(cm, params, vocab_ids, tr)
    fwd = SV.ForwardLog(svc)
    if "service" in hooks:
        hooks["service"](svc)
    server = CostModelServer(svc, max_batch=tr["max_batch"],
                             flush_us=tr["flush_us"])
    server.start(warmup=True)
    marks.append(("service warm-up", H.now()))

    pool = [texts[i] for i in FL.shuffled_blocks(len(texts),
                                                 tr["order_block"], seed)]
    order = FL.tasks(len(pool), tr["repeats"], tr["stagger"])
    annotate = H.annotation(trace_dir is not None)
    workers = [FL.Worker(server, cm["heads"],
                         np.random.default_rng((seed, 100 + w)), tr["keep"],
                         annotate) for w in range(tr["workers"])]
    search_kw = dict(beam_width=tr["beam_width"], max_steps=tr["max_steps"],
                     eval_budget=tr["eval_budget"])
    unparsed = [0]

    def search(me, text):
        res = FD.ingest(text)
        if getattr(res, "graph", None) is None:
            unparsed[0] += 1
            raise RuntimeError("the front door parsed no graph")
        beam_search(me, res.graph, **search_kw)

    def warm_up(w):
        for t in warm_texts[w::len(workers)]:
            search(workers[w], t)
    FL._run_threads(warm_up, len(workers))
    marks.append(("warm searches", H.now()))

    lock = threading.Lock()
    cursor = [0]
    state = {"t_end": None, "exhausted": False}
    go = threading.Event()

    def work(w):
        me = workers[w]
        me.recording = True
        go.wait()
        while H.now() < state["t_end"]:
            with lock:
                t = cursor[0]
                cursor[0] += 1
            if t >= len(order):
                state["exhausted"] = True
                return
            t0 = H.now()
            try:
                with annotate("bench.search"):
                    search(me, pool[order[t]])
            except Exception:        # counted by its failed call; go on
                continue
            me.searches.append((t0, H.now()))

    H.settle()
    snap0 = SV.counters(server)
    threads = [threading.Thread(target=work, args=(w,), daemon=True)
               for w in range(len(workers))]
    for t in threads:
        t.start()
    if trace_dir is not None:
        H.start_trace(trace_dir)
    setup_s = H.now() - t_process
    compiles.active = True
    with annotate("bench.window"):
        t_start = H.now()
        state["t_end"] = t_start + seconds
        go.set()
        H.sleep_until(state["t_end"])
        t_end = H.now()
    compiles.active = False
    for t in threads:
        t.join(timeout=300)
    if trace_dir is not None:
        jax.profiler.stop_trace()
    snap1 = SV.counters(server)
    server.stop()
    if any(t.is_alive() for t in threads):
        raise RuntimeError("a fleet worker did not finish its search")
    if state["exhausted"]:
        raise RuntimeError(f"the pool of {len(pool)} programs ran out "
                           f"within the window; raise 'repeats'")

    def inside(t0, t1):
        return t_start <= t0 and t1 <= t_end

    calls = [cl for w in workers for cl in w.calls if cl[0] <= t_end]
    cands = sum(k for t0, t1, k in calls
                if t1 is not None and t_start <= t1 <= t_end)
    searches = [(t0, t1) for w in workers for t0, t1 in w.searches
                if inside(t0, t1)]
    attempted = len(calls) + unparsed[0]
    failed = sum(t1 is None for _, t1, _ in calls) + unparsed[0]
    predict_s = sum(t1 - t0 for w in workers
                    for s0, s1 in w.searches if inside(s0, s1)
                    for t0, t1, _ in w.calls
                    if t1 is not None and s0 <= t0 and t1 <= s1)
    layers = {
        "counts": SV.delta(snap0, snap1),
        "searches": len(searches),
        "search_s": sum(t1 - t0 for t0, t1 in searches),
        "predict_s": predict_s,
        "forwards": fwd.between(t_start, t_end),
        "config": cm,
    }
    readings: Dict[str, float] = {}
    held = {"server": server, "svc": svc, "fwd": fwd, "params": params}
    del server, svc, fwd, params

    def check():
        rng_c = np.random.default_rng((seed, 3))
        pairs = [(g, r) for w in workers for gs, rows in w.kept
                 for g, r in zip(gs, rows)]
        pick = rng_c.permutation(len(pairs))[:tr["check_rows"]]
        chosen = [pairs[i] for i in pick]
        chosen += [(g, r) for w in workers for _, g, r in w.longest]
        ids = np.stack([REF.encode(tokens(g, cm["mode"]), vocab_ids,
                                   cm["max_seq"]) for g, _ in chosen])
        got = SV.served_z(np.stack([r for _, r in chosen]),
                          SV.norm_stats(cm), cm["heads"])
        prec = cm["matmul_precision"]
        p = held["params"]
        want = conv_rows(p, ids, cm["heads"], prec)
        bf16 = conv_rows(p, ids, cm["heads"], prec, dtype=jnp.bfloat16)
        readings.update(REF.row_gaps(bf16 if hooks.get("control") else got,
                                     want))
        readings["row_gap_mean_bf16"] = REF.row_gaps(bf16, want)[
            "row_gap_mean"]
        readings["row_gap_mean_shifted"] = REF.row_gaps(
            got, REF.forward_rows(p, ids, cm["heads"], prec))["row_gap_mean"]
        readings["rows_checked"] = float(len(chosen))
        # the cost model is dropped before the layers take the device
        held.clear()
        for w in workers:
            w.server = None
        del p, want, bf16
        gc.collect()
        readings["frontdoor_lossy_ops"] = float(sum(
            JR.lossy_ops(t, g) for t, g in zip(texts, graphs)))
        readings.update(layer_readings(cfg, tr, seed,
                                       control=bool(hooks.get("control"))))
        return H.checks(readings, tr["limits"])

    trunc = layers["counts"]
    return H.Outcome(setup_s=setup_s, window_s=t_end - t_start,
                     end_to_end={"fleet_cands_per_s":
                                 cands / (t_end - t_start)},
                     attempted=attempted, failed=failed, check=check,
                     readings=readings, layers=layers,
                     notes=[f"{cands} candidates costed in "
                            f"{t_end - t_start:.3f} s, {cursor[0]} tasks "
                            f"of {len(order)} taken, {len(searches)} "
                            f"searches done, {failed} of {attempted} "
                            f"calls failed ({unparsed[0]} unparsed)",
                            f"gateway requests {trunc['requests']:.0f}, "
                            f"cache hits {trunc['cache_hits']:.0f}",
                            "set-up after the imports: " + ", ".join(
                                f"{name} {t - marks[i][1]:.3f} s"
                                for i, (name, t) in
                                enumerate(marks[1:]))])
