"""The served cost model as the serving cells build it: its vocabulary,
weights and normalization from the seed, the program's service and
gateway around them, a recorder of the forward passes the window
dispatched, and the comparison of the rows clients received with the
plain reference."""
from __future__ import annotations

import threading
from typing import Dict, List, Sequence

import numpy as np

from chip import harness as H
from chip import programs as PG
from chip import reference as REF


def key(seed: int):
    import jax
    k = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(k, seed >> 32)


def weights(cfg: dict, seed: int) -> dict:
    """The model's weights, made on the device in one jitted call: the
    family's initialization with a unit-variance embedding (so that
    predictions differ from program to program as a trained model's
    do) and N(0, 0.02) biases (so that every bias path carries a
    value)."""
    import jax
    import jax.numpy as jnp

    def make(k):
        k1, k2 = jax.random.split(k)
        p = REF.init_conv(k1, cfg, cfg["heads"])
        leaves, tree = jax.tree.flatten(p)
        ks = jax.random.split(k2, len(leaves))
        leaves = [jax.random.normal(kk, x.shape) * jnp.float32(0.02)
                  if x.ndim == 1 else x for kk, x in zip(ks, leaves)]
        p = jax.tree.unflatten(tree, leaves)
        p["emb"] = p["emb"] * jnp.float32(50.0)
        return p
    return jax.block_until_ready(jax.jit(make)(key(seed)))


def vocab(cfg: dict, seed: int, n_programs: int = 200) -> Dict[str, int]:
    """Token ids fitted on a seeded corpus of unoptimized programs and
    their unrolled variants, with the fused-op opcode and the bfloat16
    spelling of every shape (what rewrites emit) reserved first."""
    rng = np.random.default_rng((seed, 7))
    seqs = []
    for i in range(n_programs):
        g = PG.unoptimized(PG.sample(rng, PG.family_of(i)), rng)
        seqs.append(REF.tokens(g, cfg["mode"]))
        seqs.append(REF.tokens(PG.unroll(g, 2), cfg["mode"]))
    shapes = sorted({t for s in seqs for t in s if t.endswith("xf32")})
    extra = ["xpu.fused"] + [t[:-3] + "bf16" for t in shapes]
    return REF.build_vocab(seqs, cfg["vocab_size"], extra)


def norm_stats(cfg: dict) -> Dict[str, dict]:
    return {t: {"mu": 1.0, "sigma": 1.0} for t in cfg["heads"]}


def model_config(cfg: dict):
    from repro.configs.costmodel import CostModelConfig
    return CostModelConfig(
        name=cfg["name"], vocab_size=cfg["vocab_size"],
        max_seq=cfg["max_seq"], embed_dim=cfg["embed_dim"],
        conv_filters=tuple(cfg["conv_filters"]),
        conv_channels=tuple(cfg["conv_channels"]),
        fc_dims=tuple(cfg["fc_dims"]))


def service(cfg: dict, params, vocab_ids: Dict[str, int]):
    """The program's CostModelService at the program's defaults."""
    from repro.core import tokenizer as TOK
    from repro.core.service import CostModelService
    return CostModelService(cfg["kind"], model_config(cfg), params,
                            TOK.Vocab(dict(vocab_ids)), norm_stats(cfg),
                            mode=cfg["mode"], max_seq=cfg["max_seq"])


class ForwardLog:
    """Wraps ``service.forward_dispatch`` to log each batch the gateway
    sends to the device: (time, real rows, padded rows, width)."""

    def __init__(self, svc):
        self.svc = svc
        self.rows: List[tuple] = []
        self._lock = threading.Lock()
        inner = svc.forward_dispatch

        def dispatch(ids):
            handle = inner(ids)
            with self._lock:
                self.rows.append((H.now(), ids.shape[0],
                                  svc._ladder_batch(ids.shape[0]),
                                  ids.shape[1]))
            return handle
        svc.forward_dispatch = dispatch

    def between(self, t0: float, t1: float) -> List[tuple]:
        with self._lock:
            return [r[1:] for r in self.rows if t0 <= r[0] <= t1]


COUNTERS = ("requests", "cache_hits", "batches", "batched_entries",
            "coalesced", "shed")
PHASES = ("hash_s", "encode_s", "forward_s")


def counters(server) -> Dict[str, float]:
    """The gateway's counters and the service's phase timers, now."""
    m = server.metrics
    with m._lock:
        out = {k: float(getattr(m, k)) for k in COUNTERS}
    phase = server.service.phase_stats()
    out.update({k: float(phase[k]) for k in PHASES})
    return out


def delta(a: Dict[str, float], b: Dict[str, float]) -> Dict[str, float]:
    return {k: b[k] - a[k] for k in a}


def served_z(rows: np.ndarray, stats: Dict[str, dict],
             heads: Sequence[str]) -> np.ndarray:
    """Denormalized rows, as clients received them, in normalized units."""
    rows = np.asarray(rows, np.float64)
    mu = np.asarray([stats[t]["mu"] for t in heads])
    sigma = np.asarray([stats[t]["sigma"] for t in heads])
    return (np.log1p(rows) - mu) / sigma


def row_readings(cfg: dict, params, vocab_ids: Dict[str, int],
                 graphs: Sequence, got_z: np.ndarray,
                 control: bool = False) -> Dict[str, float]:
    """Tokenize ``graphs`` afresh, run the reference over them at the
    widest width, and sum up the gaps of the rows received (normalized)
    (``reference.row_gaps``). With ``control`` the reference in bfloat16
    takes the program's place."""
    import jax.numpy as jnp
    ids = np.stack([REF.encode(REF.tokens(g, cfg["mode"]), vocab_ids,
                               cfg["max_seq"]) for g in graphs])
    prec = cfg["matmul_precision"]
    want = REF.forward_rows(params, ids, cfg["heads"], prec)
    if control:
        got_z = REF.forward_rows(params, ids, cfg["heads"], prec,
                                 dtype=jnp.bfloat16)
    return REF.row_gaps(got_z, want)
