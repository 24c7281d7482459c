"""Closed-loop autotuner fleet: workers beam-search rewrite candidates of
seeded unoptimized programs through one gateway.

Traffic parameters (``traffic/<name>.json``): ``workers`` threads, each
running ``beam_search(beam_width, max_steps, eval_budget)`` on the next
task; ``programs`` in the pool, each searched ``repeats`` times,
``stagger`` tasks apart, so later searches re-derive candidates that
earlier ones costed; ``warm_searches`` searches of other programs
before the window. The pool, the warm-up programs and the vocabulary
come from ``pool_seed``, the same for every run; the run's seed sets
the weights and shuffles the pool within consecutive blocks of
``order_block`` programs, so that every seed searches the same
programs in the window, in another order; ``max_batch`` and
``flush_us`` of the gateway; ``keep`` the share of the window's calls
kept for the comparison, ``check_rows`` how many rows of them are
compared, and ``limits`` the numbers compared (of
``reference.row_gaps``) with the limit of each. A window that would
search a program more than ``repeats`` times fails the run.
"""
from __future__ import annotations

import threading
from typing import Dict, List

import numpy as np

from chip import harness as H
from chip import programs as PG
from chip import serving as SV


def tasks(n_programs: int, repeats: int, stagger: int) -> List[int]:
    """Program index of each task, in the order workers take them: the
    r-th search of program i sits at i + r * stagger."""
    order = sorted((i + r * stagger, r, i) for i in range(n_programs)
                   for r in range(repeats))
    return [i for _, _, i in order]


def shuffled_blocks(n: int, block: int, seed: int) -> List[int]:
    """0..n-1 with each run of ``block`` consecutive indices shuffled
    by ``seed``: any prefix that ends on a block boundary holds the
    same indices for every seed."""
    rng = np.random.default_rng(seed)
    return [int(i) for b in range(0, n, block)
            for i in b + rng.permutation(min(block, n - b))]


class Worker:
    """One fleet worker: the gateway behind a proxy that times and
    counts every ``predict_all`` the search makes."""

    def __init__(self, server, heads, rng, keep: float, annotate):
        self.server = server
        self.order = heads
        self.rng = rng
        self.keep = keep
        self.annotate = annotate
        self.calls = []          # (t0, t1 or None if it failed, n_graphs)
        self.searches = []       # (t0, t1)
        self.kept = []           # (graphs, denormalized rows)
        self.longest = []        # (n_ops, graph, row)
        self.recording = False

    @property
    def heads(self):
        return self.server.heads

    def resolve_target(self, target):
        return self.server.resolve_target(target)

    def predict_all(self, graphs):
        t0 = H.now()
        try:
            with self.annotate("bench.predict_all"):
                out = self.server.predict_all(graphs)
        except Exception:
            if self.recording:
                self.calls.append((t0, None, len(graphs)))
            raise
        t1 = H.now()
        if self.recording:
            self.calls.append((t0, t1, len(graphs)))
            rows = np.stack([out[t] for t in self.order], axis=1)
            if self.rng.random() < self.keep:
                self.kept.append((list(graphs), rows))
            for g, r in zip(graphs, rows):
                n = len(g.ops)
                if len(self.longest) < 4 or n > self.longest[0][0]:
                    self.longest.append((n, g, r))
                    self.longest.sort(key=lambda e: e[0])
                    del self.longest[:-4]
        return out


def run(cell, *, seed, seconds, trace_dir, t_process, compiles, hooks):
    import jax
    from repro.core.server import CostModelServer
    from repro.opt.search import beam_search

    cfg, tr = cell.config, cell.traffic
    marks = [("start", H.now())]
    vocab = SV.vocab(cfg, tr["pool_seed"])
    marks.append(("vocabulary", H.now()))
    params = SV.weights(cfg, seed)
    marks.append(("weights", H.now()))
    svc = SV.service(cfg, params, vocab)
    fwd = SV.ForwardLog(svc)
    if "service" in hooks:
        hooks["service"](svc)
    server = CostModelServer(svc, max_batch=tr["max_batch"],
                             flush_us=tr["flush_us"])
    server.start(warmup=True)
    marks.append(("service warm-up", H.now()))

    rng = np.random.default_rng((tr["pool_seed"], 1))
    fixed = [PG.unoptimized(PG.sample(rng, PG.family_of(i)), rng)
             for i in range(tr["programs"])]
    pool = [fixed[i] for i in shuffled_blocks(len(fixed), tr["order_block"],
                                              seed)]
    warm_rng = np.random.default_rng((tr["pool_seed"], 2))
    warm = [PG.unoptimized(PG.sample(warm_rng, PG.family_of(i)), warm_rng)
            for i in range(tr["warm_searches"])]
    marks.append(("programs", H.now()))
    order = tasks(len(pool), tr["repeats"], tr["stagger"])
    annotate = H.annotation(trace_dir is not None)
    workers = [Worker(server, cfg["heads"],
                      np.random.default_rng((seed, 100 + w)), tr["keep"],
                      annotate) for w in range(tr["workers"])]
    search_kw = dict(beam_width=tr["beam_width"], max_steps=tr["max_steps"],
                     eval_budget=tr["eval_budget"])

    def warm_up(w):
        for g in warm[w::len(workers)]:
            beam_search(workers[w], g, **search_kw)
    _run_threads(warm_up, len(workers))
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
                    beam_search(me, pool[order[t]], **search_kw)
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
        t.join(timeout=120)
    if trace_dir is not None:
        jax.profiler.stop_trace()
    snap1 = SV.counters(server)
    server.stop()
    if any(t.is_alive() for t in threads):
        raise RuntimeError("a fleet worker did not finish its search")
    if state["exhausted"]:
        raise RuntimeError(f"the pool of {len(pool)} programs ran out "
                           f"within the window; raise 'programs'")

    def inside(t0, t1):
        return t_start <= t0 and t1 <= t_end

    calls = [c for w in workers for c in w.calls if c[0] <= t_end]
    cands = sum(n for t0, t1, n in calls
                if t1 is not None and t_start <= t1 <= t_end)
    attempted = len(calls)
    failed = sum(t1 is None for _, t1, _ in calls)
    search_s = sum(t1 - t0 for w in workers for t0, t1 in w.searches
                   if inside(t0, t1))
    predict_s = sum(t1 - t0 for w in workers
                    for s0, s1 in w.searches if inside(s0, s1)
                    for t0, t1, _ in w.calls
                    if t1 is not None and s0 <= t0 and t1 <= s1)
    layers = {
        "counts": SV.delta(snap0, snap1),
        "search_s": search_s, "predict_s": predict_s,
        "forwards": fwd.between(t_start, t_end),
        "config": cfg,
    }
    readings: Dict[str, float] = {}

    def check():
        rng_c = np.random.default_rng((seed, 3))
        pairs = [(g, r) for w in workers for gs, rows in w.kept
                 for g, r in zip(gs, rows)]
        pick = rng_c.permutation(len(pairs))[:tr["check_rows"]]
        chosen = [pairs[i] for i in pick]
        chosen += [(g, r) for w in workers for _, g, r in w.longest]
        graphs = [g for g, _ in chosen]
        got = SV.served_z(np.stack([r for _, r in chosen]),
                          SV.norm_stats(cfg), cfg["heads"])
        readings.update(SV.row_readings(
            cfg, params, vocab, graphs, got,
            control=hooks.get("control", False)))
        return H.checks(readings, tr["limits"])

    return H.Outcome(setup_s=setup_s, window_s=t_end - t_start,
                     end_to_end={"fleet_cands_per_s":
                                 cands / (t_end - t_start)},
                     attempted=attempted, failed=failed, check=check,
                     readings=readings, layers=layers,
                     notes=[f"{cands} candidates costed in "
                            f"{t_end - t_start:.3f} s, {cursor[0]} tasks "
                            f"of {len(order)} taken, {failed} of "
                            f"{attempted} predict_all calls failed",
                            "set-up after the imports: " + ", ".join(
                                f"{name} {t - marks[i][1]:.3f} s"
                                for i, (name, t) in
                                enumerate(marks[1:]))])


def _run_threads(fn, n: int) -> None:
    errs = []

    def wrap(i):
        try:
            fn(i)
        except Exception as e:
            errs.append(e)
    ts = [threading.Thread(target=wrap, args=(i,), daemon=True)
          for i in range(n)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    if errs:
        raise errs[0]
