"""Training on a stream of fresh programs: ``TrainEngine.fit`` over a
seeded corpus of sampled programs and their unrolled variants.

Traffic parameters (``traffic/<name>.json``): ``programs`` sampled from
``corpus_seed`` (the same corpus for every run; the run's seed sets
the weights and the order of the batches), each joined by its variants
unrolled by every factor in ``unroll_factors`` (equal shares); ``batch_size``; ``warm_steps`` run
before the window (the first compiles, the first three are compared
with the reference); ``optimizer`` the AdamW settings the engine uses
(learning rate, decay, moments, clipping, schedule), which the
reference follows; ``limits``, the numbers compared (of those the
check reads) and the limit of each.

``fit`` runs a fixed number of steps, so the window is closed the way a
scheduler preempts a job: at the first step that ends past the window
the run sends itself SIGTERM, which the engine's ``install_sigterm``
handler turns into a stop after that step; ``fit`` then syncs on the
last loss. A proxy around the engine's jitted step keeps the outputs of
the first steps for the comparison, and a wrapper around the corpus
records which rows each batch took.
"""
from __future__ import annotations

import os
import signal
from typing import Dict, List

import numpy as np

from chip import flops
from chip import harness as H
from chip import programs as PG
from chip import reference as REF
from chip import serving as SV

STEPS_CHECKED = 3


def targets(g) -> Dict[str, float]:
    """Positive labels that follow the program's structure."""
    n_ops = len(g.ops)
    ew = sum(op.opcode in PG.ELEMENTWISE for op in g.ops)
    elems = sum(float(np.prod(g.values[op.result].shape)) for op in g.ops)
    return {"register_pressure": 8.0 + 0.5 * n_ops,
            "valu_utilization": 1.0 + 100.0 * ew / max(n_ops, 1),
            "latency_us": 1.0 + elems / 1e5}


def corpus(cfg: dict, tr: dict):
    """Token rows, true lengths and labels of the corpus's programs."""
    rng = np.random.default_rng(tr["corpus_seed"])
    seqs, labels = [], []
    for i in range(tr["programs"]):
        g = PG.sample(rng, PG.family_of(i))
        for f in tr["unroll_factors"]:
            gf = PG.unroll(g, f) if f > 1 else g
            seqs.append(REF.tokens(gf, cfg["mode"]))
            labels.append(targets(gf))
    vocab = REF.build_vocab(seqs, cfg["vocab_size"])
    lens = np.asarray([min(len(s), cfg["max_seq"]) for s in seqs], np.int32)
    slack = REF.conv_slack(cfg["conv_filters"])
    widths = np.asarray([REF.bucket(len(s), cfg["max_seq"], slack)
                         for s in seqs])
    ids = {int(b): np.stack([REF.encode(seqs[i], vocab, int(b))
                             for i in np.flatnonzero(widths == b)])
           for b in np.unique(widths)}
    rows = {b: np.flatnonzero(widths == b) for b in ids}
    y = {t: np.asarray([lab[t] for lab in labels], np.float32)
         for t in cfg["heads"]}
    return seqs, vocab, ids, rows, lens, widths, y


def dataset(cfg, vocab, ids, rows, lens, y, seen: List[np.ndarray]):
    """The program's bucketed CostDataset over the corpus, recording the
    rows each batch gathers (in the order the loader asks)."""
    from repro.core import tokenizer as TOK
    from repro.ir.dataset import CostDataset

    class Recorded(CostDataset):
        def row_ids(self, idx, width):
            seen.append(np.asarray(idx).copy())
            return super().row_ids(idx, width)

    return Recorded(ids=None, targets=y, vocab=TOK.Vocab(dict(vocab)),
                    mode=cfg["mode"], max_seq=cfg["max_seq"],
                    seq_lens=lens, bucket_ids=ids, bucket_rows=rows)


def run(cell, *, seed, seconds, trace_dir, t_process, compiles, hooks):
    import jax
    import jax.numpy as jnp
    from repro.core import trainer as TR

    cfg, tr = cell.config, cell.traffic
    opt = tr["optimizer"]
    seqs, vocab, ids, rows, lens, widths, y = corpus(cfg, tr)
    seen: List[np.ndarray] = []
    train = dataset(cfg, vocab, ids, rows, lens, y, seen)
    kept = {}
    outputs = []
    make_step = TR.make_train_step

    def recording_make_step(apply_fn, opt_cfg, heads=None):
        real = make_step(apply_fn, opt_cfg, heads)
        if "step" in hooks:
            real = hooks["step"](real)

        def step(state, ids_, yy):
            new, loss = real(state, ids_, yy)
            outputs.append(loss)
            k = len(outputs)
            # copied to the host during warm-up, so that they hold no
            # device memory through the window
            if k == 1:
                kept["m1"] = jax.device_get(new[1]["m"])
            if k == STEPS_CHECKED:
                kept["p3"] = jax.device_get(new[0])
            return new, loss
        return step

    k0 = tr["warm_steps"]
    state = {"t_start": None, "last": None}
    annotate = H.annotation(trace_dir is not None)

    def on_step(step, dt):
        if step == k0:
            jax.block_until_ready(outputs[-1])
            if trace_dir is not None:
                H.start_trace(trace_dir)
            state["setup_s"] = H.now() - t_process
            state["ann"] = annotate("bench.window")
            state["ann"].__enter__()
            compiles.active = True
            state["t_start"] = H.now()
        elif state["t_start"] is not None and state["last"] is None \
                and H.now() - state["t_start"] >= seconds:
            state["last"] = step
            os.kill(os.getpid(), signal.SIGTERM)

    H.settle()
    engine = TR.TrainEngine(
        cfg["kind"], SV.model_config(cfg), tuple(cfg["heads"]),
        steps=tr["steps_cap"], batch_size=tr["batch_size"],
        lr=opt["lr"], weight_decay=opt["weight_decay"], seed=seed,
        log_every=tr["steps_cap"] + 1, install_sigterm=True)
    TR.make_train_step = recording_make_step
    try:
        res = engine.fit(train, on_step=on_step)
    finally:
        TR.make_train_step = make_step
        signal.signal(signal.SIGTERM, signal.SIG_DFL)
    t_end = H.now()
    compiles.active = False
    if state.get("ann") is not None:
        state["ann"].__exit__(None, None, None)
    if trace_dir is not None:
        jax.profiler.stop_trace()
    del res
    n_steps = len(outputs)
    if state["last"] is None or n_steps != state["last"]:
        raise RuntimeError(f"the engine ran {n_steps} steps; the window "
                           f"asked it to stop after {state['last']}")
    batches = seen[:n_steps]
    win = batches[k0:]
    tokens = sum(int(lens[b].sum()) for b in win)
    positions = sum(len(b) * int(widths[b].max()) for b in win)
    window_s = t_end - state["t_start"]
    step_widths = [int(widths[b].max()) for b in batches]
    layers = {"config": cfg, "tokens": tokens, "positions": positions,
              "steps": len(win), "widths": step_widths[k0:],
              "model_flops": 3 * sum(
                  int(lens[b].sum()) * flops.matmul_flops_per_position(cfg)
                  + len(b) * flops.matmul_flops_per_row(cfg) for b in win)}
    losses_prog = [float(x) for x in outputs[:STEPS_CHECKED]]
    m1, p3 = kept["m1"], kept["p3"]
    del outputs[:]
    limits = tr["limits"]
    readings: Dict[str, float] = {}

    def check():
        p0 = REF.init_conv(jax.random.PRNGKey(seed), cfg, cfg["heads"])
        ly = {t: np.log1p(y[t].astype(np.float64)) for t in cfg["heads"]}
        yn = np.stack([(ly[t] - ly[t].mean()) / (ly[t].std() + 1e-8)
                       for t in cfg["heads"]], axis=1).astype(np.float32)
        feed = []
        for b in batches[:STEPS_CHECKED]:
            w = int(widths[b].max())
            x = np.zeros((len(b), w), np.int32)
            for r, i in enumerate(b):
                x[r] = REF.encode(seqs[i], vocab, w)
            feed.append((x, yn[b]))
        prec = cfg["matmul_precision"]
        losses, g1, p3r = REF.train_steps(p0, feed, cfg["heads"], opt, prec)
        norms = [float(np.linalg.norm(x)) for x in jax.tree.leaves(g1)]
        med = float(np.median(norms))
        # leaves whose reference gradient is nought to rounding (a bias
        # under the max-pool, say) move under Adam by round-off alone
        exclude = {i for i, n in enumerate(norms) if n < 1e-3 * med}
        d_ref = jax.tree.map(lambda a, b: a - b, p3r, p0)

        if hooks.get("control"):
            # the reference in bfloat16 takes the program's place
            got = REF.train_steps(p0, feed, cfg["heads"], opt, prec,
                                  dtype=jnp.bfloat16)
        else:
            got = (losses_prog,
                   jax.tree.map(lambda m: m / (1 - opt["b1"]), m1), p3)
        losses_got, g_got, p_got = got
        d_got = jax.tree.map(lambda a, b: a - b, p_got, p0)
        grad = REF.leaf_numbers(g_got, g1, exclude)
        update = REF.leaf_numbers(d_got, d_ref, exclude)
        # a head's bias gradient is the first batch's mean residual of
        # that head: it reads the forward pass through every layer, free
        # of the ReLU and max-pool switches that move the gradients below
        bias = [d for n, d in zip(grad["name"], grad["diff"])
                if n.startswith("['heads']") and n.endswith("['b']")]
        readings.update({
            "loss_gap": max(abs(a - b) / abs(b)
                            for a, b in zip(losses_got, losses)),
            "grad_gap": float(grad["gap"].max()),
            "grad_diff": float(grad["diff"].max()),
            "residual_gap": float(max(bias)),
            "update_gap": float(update["gap"].max())})
        return H.checks(readings, limits)

    return H.Outcome(
        setup_s=state["setup_s"], window_s=window_s,
        end_to_end={"train_tokens_per_s": tokens / window_s},
        attempted=len(win), failed=0, check=check, readings=readings,
        layers=layers,
        notes=[f"{len(win)} steps, {tokens} tokens of {positions} "
               f"positions in {window_s:.3f} s; widths "
               f"{sorted(set(step_widths))}; first losses "
               f"{losses_prog}"])
