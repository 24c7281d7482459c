"""The plain reference the benchmark compares the program with.

Nothing here imports the program. It reads the input programs (the
system's ``Graph`` type) only through their public fields, tokenizes
them itself (the paper's Fig. 4 layouts), and runs the conv1d cost
model in straightforward ``jax.numpy``: the embedding gather, each
'same'-padded convolution as a sum of shifted matrix products, the
max-pool over positions, the FC stack and the linear heads. Training
adds the joint MSE, its gradients, global-norm clipping and AdamW.

The reference computes in the precision the configuration states:
float32 with matrix products at ``matmul_precision`` (``default`` is one
bfloat16 pass per float32 product on a TPU). The control
(``dtype=bfloat16``) is the nearest precision below: the parameters
cast to bfloat16 and every activation kept in bfloat16, the products
at the same setting; in training the master weights, the loss and
AdamW stay float32, as in mixed-precision training.
"""
from __future__ import annotations

from collections import Counter
from typing import Dict, Iterable, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

PAD, UNK, BOS, EOS, SEP = "<pad>", "<unk>", "<bos>", "<eos>", "<sep>"
SPECIALS = (PAD, UNK, BOS, EOS, SEP)


# ------------------------------------------------------------- tokens
def shape_token(t) -> str:
    dims = "x".join(str(d) for d in t.shape)
    return f"{dims}x{t.dtype}" if t.shape else t.dtype


def _ssa(g, vid: int) -> str:
    return f"%arg{vid}" if vid < g.n_args else f"%{vid - g.n_args}"


def tokens(g, mode: str) -> List[str]:
    """Input shapes, SEP, the op sequence, SEP, output shapes. ``ops``
    gives each op as opcode + result shape; ``ops_operands`` adds the
    result's SSA name before and the operands' names after the opcode."""
    out = [BOS] + [shape_token(g.values[i]) for i in range(g.n_args)]
    out.append(SEP)
    for op in g.ops:
        if mode == "ops_operands":
            out.append(_ssa(g, op.result))
        out.append(f"xpu.{op.opcode}")
        if mode == "ops_operands":
            out.extend(_ssa(g, o) for o in op.operands)
        out.append(shape_token(g.values[op.result]))
    out.append(SEP)
    out += [shape_token(g.values[o]) for o in g.outputs]
    out.append(EOS)
    return out


def build_vocab(token_seqs: Iterable[Sequence[str]], cap: int,
                extra: Sequence[str] = ()) -> Dict[str, int]:
    """Specials, then ``extra``, then tokens by falling count (ties by
    spelling), up to ``cap`` ids."""
    counts = Counter()
    for seq in token_seqs:
        counts.update(seq)
    vocab = {t: i for i, t in enumerate(SPECIALS)}
    for t in list(extra) + sorted(counts, key=lambda t: (-counts[t], t)):
        if len(vocab) >= cap:
            break
        vocab.setdefault(t, len(vocab))
    return vocab


def encode(toks: Sequence[str], vocab: Dict[str, int], width: int
           ) -> np.ndarray:
    """Ids truncated or zero-padded to ``width``; unknown tokens -> UNK."""
    unk = vocab[UNK]
    ids = [vocab.get(t, unk) for t in toks[:width]]
    out = np.zeros((width,), np.int32)
    out[:len(ids)] = ids
    return out


def bucket(n_tokens: int, max_seq: int, slack: int, lo: int = 32) -> int:
    """Smallest power-of-two width from ``lo`` (capped at max_seq) that
    holds ``n_tokens + slack``; the conv tower's boundary cone needs the
    slack for padded positions to leave real ones unchanged."""
    b = lo
    while b < max_seq and n_tokens + slack > b:
        b *= 2
    return min(b, max_seq)


def conv_slack(conv_filters: Sequence[int]) -> int:
    return 2 * sum(fs // 2 for fs in conv_filters)


# ------------------------------------------------------------ weights
def init_conv(key, cfg: dict, heads: Sequence[str]) -> dict:
    """The conv1d cost model's initialization: N(0, 0.02) embedding,
    N(0, 1/fan_in) matrices, zero biases, one (F, 1) head per target,
    keys split as the model family splits them."""
    filt, chans = cfg["conv_filters"], cfg["conv_channels"]
    n_conv = len(filt)
    ks = jax.random.split(key, 2 + n_conv + 3)
    normal = jax.random.normal
    p = {"emb": normal(ks[0], (cfg["vocab_size"], cfg["embed_dim"]))
         * jnp.float32(0.02), "convs": []}
    c_in = cfg["embed_dim"]
    for i, (fs, c_out) in enumerate(zip(filt, chans)):
        p["convs"].append({
            "w": normal(ks[1 + i], (fs, c_in, c_out))
            * jnp.float32(1.0 / np.sqrt(fs * c_in)),
            "b": jnp.zeros((c_out,))})
        c_in = c_out
    dims = [c_in, *cfg["fc_dims"]]
    p["fc"] = [{"w": normal(ks[1 + n_conv + i], (dims[i], dims[i + 1]))
                * jnp.float32(1.0 / np.sqrt(dims[i])),
                "b": jnp.zeros((dims[i + 1],))}
               for i in range(len(dims) - 1)]
    hk = jax.random.split(ks[-1], len(heads))
    f = dims[-1]
    p["heads"] = {t: {"w": normal(k, (f, 1)) * jnp.float32(1 / np.sqrt(f)),
                      "b": jnp.zeros((1,))} for t, k in zip(heads, hk)}
    return p


# ------------------------------------------------------------ forward
def forward(p, ids, heads: Sequence[str], precision="default",
            dtype=jnp.float32):
    """(B, S) ids -> (B, n_heads) normalized predictions (float32),
    computed in ``dtype``."""
    p = jax.tree.map(lambda a: a.astype(dtype), p)

    def mm(a, w):
        return jnp.matmul(a, w, precision=precision)

    mask = (ids != 0).astype(dtype)
    x = p["emb"][ids] * mask[..., None]
    s = ids.shape[1]
    for layer in p["convs"]:
        w = layer["w"]
        fs = w.shape[0]
        xp = jnp.pad(x, ((0, 0), ((fs - 1) // 2, fs // 2), (0, 0)))
        y = sum(mm(xp[:, k:k + s], w[k]) for k in range(fs))
        x = jax.nn.relu(y + layer["b"])
    x = x.max(axis=1)
    for layer in p["fc"]:
        x = jax.nn.relu(mm(x, layer["w"]) + layer["b"])
    out = jnp.stack([mm(x, p["heads"][t]["w"])[:, 0] + p["heads"][t]["b"][0]
                     for t in heads], axis=1)
    return out.astype(jnp.float32)


def forward_rows(p, ids: np.ndarray, heads, precision: str, *,
                 dtype=jnp.float32, block: int = 256) -> np.ndarray:
    """``forward`` over many rows, ``block`` rows at a time."""
    fn = jax.jit(lambda pp, x: forward(pp, x, heads, precision, dtype))
    out = []
    for i in range(0, len(ids), block):
        part = ids[i:i + block]
        n = len(part)
        if n < block:
            part = np.concatenate(
                [part, np.zeros((block - n, part.shape[1]), part.dtype)])
        out.append(np.asarray(fn(p, part))[:n])
    return np.concatenate(out) if out else np.zeros((0, len(heads)))


def row_gaps(got: np.ndarray, want: np.ndarray) -> Dict[str, float]:
    """|got - want| of every row and head, each head's gap in units of
    the spread (standard deviation) of its reference values, summed up
    as the widest (``row_gap``) and the mean (``row_gap_mean``)."""
    scale = np.maximum(want.std(axis=0), 1e-12)
    e = np.abs(got - want) / scale
    return {"row_gap": float(e.max()), "row_gap_mean": float(e.mean())}


# ------------------------------------------------------------ training
def loss(p, ids, y, heads, precision, dtype=jnp.float32):
    pred = forward(p, ids, heads, precision, dtype)
    return jnp.mean(jnp.mean(jnp.square(pred - y), axis=0))


def lr_at(step: int, opt: dict) -> float:
    """Linear warm-up, then cosine decay to ``min_lr_ratio``."""
    warm = min(step / max(opt["warmup_steps"], 1), 1.0)
    frac = np.clip((step - opt["warmup_steps"])
                   / max(opt["total_steps"] - opt["warmup_steps"], 1),
                   0.0, 1.0)
    r = opt["min_lr_ratio"]
    return opt["lr"] * warm * (r + (1 - r) * 0.5 * (1 + np.cos(np.pi * frac)))


def adamw_step(p, g, m, v, step: int, opt: dict):
    """Global-norm clipping, then one AdamW update (decay on matrices)."""
    leaves = jax.tree.leaves(g)
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in leaves))
    g = jax.tree.map(lambda x: x * jnp.minimum(
        1.0, opt["clip_norm"] / jnp.maximum(norm, 1e-9)), g)
    b1, b2 = opt["b1"], opt["b2"]
    lr = lr_at(step, opt)
    m = jax.tree.map(lambda a, b: b1 * a + (1 - b1) * b, m, g)
    v = jax.tree.map(lambda a, b: b2 * a + (1 - b2) * b * b, v, g)

    def upd(w, mm, vv):
        u = (mm / (1 - b1 ** step)) / (jnp.sqrt(vv / (1 - b2 ** step))
                                       + opt["eps"])
        if w.ndim >= 2:
            u = u + opt["weight_decay"] * w
        return w - lr * u
    return jax.tree.map(upd, p, m, v), g, m, v


def train_steps(p0, batches, heads, opt: dict, precision: str,
                dtype=jnp.float32):
    """Three (or len(batches)) reference training steps from ``p0``:
    each step's loss, the first step's clipped gradient, and the
    parameters after the last step."""
    step = jax.jit(lambda p, ids, y: jax.value_and_grad(loss)(
        p, ids, y, heads, precision, dtype))
    upd = jax.jit(lambda p, g, m, v, k: adamw_step(p, g, m, v, k, opt),
                  static_argnums=4)
    p = p0
    m = jax.tree.map(jnp.zeros_like, p0)
    v = jax.tree.map(jnp.zeros_like, p0)
    losses, g1 = [], None
    for k, (ids, y) in enumerate(batches, start=1):
        val, g = step(p, ids, y)
        p, gc, m, v = upd(p, g, m, v, k)
        losses.append(float(val))
        if k == 1:
            g1 = gc
    return losses, g1, p


def leaf_numbers(got, want, exclude=()) -> Dict[str, np.ndarray]:
    """Per leaf, against the larger of the leaf's own reference norm and
    the median leaf's: the gap of the norms, |norm(got) - norm(want)|
    (``gap``), and the norm of the difference, norm(got - want)
    (``diff``), with each leaf's path (``name``). ``exclude`` lists leaf
    indices left out (their reference gradient is nought)."""
    paths, _ = jax.tree_util.tree_flatten_with_path(want)
    names = np.asarray([jax.tree_util.keystr(k) for k, _ in paths])
    pairs = [(np.asarray(a, np.float64), np.asarray(b, np.float64))
             for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want))]
    wn = np.asarray([np.linalg.norm(b) for _, b in pairs])
    scale = np.maximum(wn, np.median(wn))
    keep = [i for i in range(len(pairs)) if i not in exclude]
    gn = np.asarray([np.linalg.norm(a) for a, _ in pairs])
    dn = np.asarray([np.linalg.norm(a - b) for a, b in pairs])
    return {"name": names[keep], "gap": (np.abs(gn - wn) / scale)[keep],
            "diff": (dn / scale)[keep]}
