"""The paper's three regressor families, in pure JAX.

1. FC bag-of-tokens        — mean-pooled embeddings -> FC stack (worst RMSE).
2. LSTM                    — lax.scan LSTM over the sequence (middle).
3. Conv1D+MaxPool+FC       — 6 stacked Conv1D (filter sizes per config),
                             MaxPool1D, 3 FC layers (best RMSE; Figs 5/6).

All models share the embedding layer (dim 64 per the paper) and are split
into a shared ``encode(params, ids) -> features`` stage plus regression
heads. Two head layouts exist:

* **single-head** (legacy): the final linear layer predicts one scalar
  target; ``apply(params, ids)`` returns a ``(B,)`` array. This is the
  layout produced by ``*_init(key, cfg)`` with no ``heads`` argument and
  is kept so existing single-target callers keep working.
* **multi-head**: ``*_init(key, cfg, heads=("register_pressure", ...))``
  replaces the final layer with a dict of per-target linear heads over the
  shared features; ``apply(params, ids)`` returns
  ``{target: (B,) array}``. One encoder pass serves every target.

Params are plain dicts with matching ``*_axes`` (which accept the same
``heads`` knob) for the sharded 100M-scale driver.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.layers import _init

# Canonical multi-target head set (every analyzer target, in analyzer order).
DEFAULT_HEADS: Tuple[str, ...] = (
    "register_pressure", "valu_utilization", "latency_us")


# --------------------------------------------------------------- embedding
def embed_init(key, cfg):
    return {"emb": _init(key, (cfg.vocab_size, cfg.embed_dim), scale=0.02)}


def _mask(ids):
    return (ids != 0).astype(jnp.float32)  # PAD id is 0


# ------------------------------------------------------------------- heads
def heads_init(key, feat_dim: int, heads: Sequence[str]) -> Dict[str, Any]:
    """One linear head per target over shared ``feat_dim`` features."""
    ks = jax.random.split(key, max(len(heads), 1))
    return {t: {"w": _init(k, (feat_dim, 1)), "b": jnp.zeros((1,))}
            for t, k in zip(heads, ks)}


def heads_axes(heads: Sequence[str]):
    return {t: {"w": (None, None), "b": (None,)} for t in heads}


def scalar_head(head_p: Dict[str, Any], feats):
    """The one {"w": (F, 1), "b": (1,)} linear-readout contract."""
    return (feats @ head_p["w"] + head_p["b"])[..., 0]


def apply_heads(heads_p: Dict[str, Any], feats) -> Dict[str, Any]:
    return {t: scalar_head(h, feats) for t, h in heads_p.items()}


def model_heads(params) -> Optional[Tuple[str, ...]]:
    """Head names of a multi-head param tree, or None for single-head."""
    if isinstance(params, dict) and "heads" in params:
        return tuple(params["heads"])
    return None


def _finish(p, feats, single_head_fn):
    """Dispatch features to the multi-head dict or the legacy scalar head."""
    if "heads" in p:
        return apply_heads(p["heads"], feats)
    return single_head_fn(feats)


def fc_stack(p, x):
    """Hidden FC layers of an fc/conv param tree -> shared features.

    Multi-head layout: every ``p["fc"]`` layer is hidden (relu'd).
    Single-head layout: the last layer is the scalar head, so it is
    excluded here and applied by :func:`fc_scalar_head`."""
    hidden = p["fc"] if "heads" in p else p["fc"][:-1]
    for layer in hidden:
        x = jax.nn.relu(x @ layer["w"] + layer["b"])
    return x


def fc_scalar_head(p, feats):
    return scalar_head(p["fc"][-1], feats)


def fc_finish(p, x):
    """Pooled features -> fc_stack -> head outputs, for either layout
    (shared by conv_apply and the fused-kernel tower in kernels/ops.py),
    under the ``fc`` and ``heads`` named scopes."""
    with jax.named_scope("fc"):
        feats = fc_stack(p, x)
    with jax.named_scope("heads"):
        return _finish(p, feats, lambda f: fc_scalar_head(p, f))


# --------------------------------------------------------------- FC (BoT)
def fc_init(key, cfg, heads: Optional[Sequence[str]] = None) -> Dict[str, Any]:
    ks = jax.random.split(key, 5)
    p = {**embed_init(ks[0], cfg)}
    dims = [cfg.embed_dim, *cfg.fc_dims] + ([] if heads else [1])
    p["fc"] = [{"w": _init(ks[1 + i % 3], (dims[i], dims[i + 1])),
                "b": jnp.zeros((dims[i + 1],))}
               for i in range(len(dims) - 1)]
    if heads:
        p["heads"] = heads_init(ks[4], cfg.fc_dims[-1], heads)
    return p


def fc_axes(cfg, heads: Optional[Sequence[str]] = None):
    n_fc = len(cfg.fc_dims) + (0 if heads else 1)
    ax = {"emb": ("vocab", "embed"),
          "fc": [{"w": ("ffn", None) if i else ("embed", "ffn"),
                  "b": (None,)} for i in range(n_fc)]}
    if heads:
        ax["heads"] = heads_axes(heads)
    return ax


def fc_encode(p, ids):
    """Bag-of-tokens pooling + the hidden FC stack -> shared features."""
    m = _mask(ids).astype(p["emb"].dtype)
    x = p["emb"][ids] * m[..., None]
    x = x.sum(1) / jnp.maximum(m.sum(1, keepdims=True), 1.0)  # bag of tokens
    return fc_stack(p, x)


def fc_apply(p, ids):
    return _finish(p, fc_encode(p, ids), lambda f: fc_scalar_head(p, f))


# --------------------------------------------------------------- LSTM
def lstm_init(key, cfg,
              heads: Optional[Sequence[str]] = None) -> Dict[str, Any]:
    ks = jax.random.split(key, 5)
    h = cfg.lstm_hidden
    p = {**embed_init(ks[0], cfg),
         "wx": _init(ks[1], (cfg.embed_dim, 4 * h)),
         "wh": _init(ks[2], (h, 4 * h)),
         "b": jnp.zeros((4 * h,))}
    if heads:
        p["heads"] = heads_init(ks[3], h, heads)
    else:
        p["head"] = {"w": _init(ks[3], (h, 1)), "b": jnp.zeros((1,))}
    return p


def lstm_axes(cfg, heads: Optional[Sequence[str]] = None):
    ax = {"emb": ("vocab", "embed"), "wx": ("embed", "ffn"),
          "wh": (None, "ffn"), "b": (None,)}
    if heads:
        ax["heads"] = heads_axes(heads)
    else:
        ax["head"] = {"w": (None, None), "b": (None,)}
    return ax


def lstm_encode(p, ids):
    """Masked LSTM scan -> final hidden state as shared features.

    Mask and initial state follow the embedding dtype so bf16-cast
    params run a bf16 scan instead of silently promoting back to f32."""
    x = p["emb"][ids]                       # (B, S, E)
    m = _mask(ids).astype(x.dtype)
    B = x.shape[0]
    h_dim = p["wh"].shape[0]
    xw = x @ p["wx"] + p["b"]

    def step(carry, inp):
        h, c = carry
        xt, mt = inp
        gates = xt + h @ p["wh"]
        i, f, g, o = jnp.split(gates, 4, axis=-1)
        i, f, o = jax.nn.sigmoid(i), jax.nn.sigmoid(f + 1.0), jax.nn.sigmoid(o)
        g = jnp.tanh(g)
        c_new = f * c + i * g
        h_new = o * jnp.tanh(c_new)
        keep = mt[:, None]
        return (h_new * keep + h * (1 - keep),
                c_new * keep + c * (1 - keep)), None

    h0 = jnp.zeros((B, h_dim), x.dtype)
    (h, _), _ = jax.lax.scan(step, (h0, h0),
                             (xw.transpose(1, 0, 2), m.T))
    return h


def lstm_apply(p, ids):
    return _finish(p, lstm_encode(p, ids),
                   lambda f: scalar_head(p["head"], f))


# ------------------------------------------------- Conv1D + MaxPool + FC
def conv_init(key, cfg,
              heads: Optional[Sequence[str]] = None) -> Dict[str, Any]:
    ks = jax.random.split(key, 2 + cfg.n_conv + 3)
    p = {**embed_init(ks[0], cfg), "convs": []}
    c_in = cfg.embed_dim
    for i, (fs, c_out) in enumerate(zip(cfg.conv_filters, cfg.conv_channels)):
        p["convs"].append({
            "w": _init(ks[1 + i], (fs, c_in, c_out),
                       scale=1.0 / np.sqrt(fs * c_in)),
            "b": jnp.zeros((c_out,))})
        c_in = c_out
    dims = [c_in, *cfg.fc_dims] + ([] if heads else [1])
    p["fc"] = [{"w": _init(ks[1 + cfg.n_conv + i], (dims[i], dims[i + 1])),
                "b": jnp.zeros((dims[i + 1],))}
               for i in range(len(dims) - 1)]
    if heads:
        p["heads"] = heads_init(ks[-1], cfg.fc_dims[-1], heads)
    return p


def conv_axes(cfg, heads: Optional[Sequence[str]] = None):
    n_fc = len(cfg.fc_dims) + (0 if heads else 1)
    ax = {"emb": ("vocab", "embed"),
          "convs": [{"w": (None, None, "ffn"), "b": ("ffn",)}
                    for _ in range(cfg.n_conv)],
          "fc": [{"w": ("ffn", None), "b": (None,)}
                 for _ in range(n_fc)]}
    if heads:
        ax["heads"] = heads_axes(heads)
    return ax


def conv1d(x, w, b):
    """'same'-padded 1D conv. x: (B, S, Cin); w: (fs, Cin, Cout)."""
    fs = w.shape[0]
    out = jax.lax.conv_general_dilated(
        x, w, window_strides=(1,),
        padding=[((fs - 1) // 2, fs // 2)],
        dimension_numbers=("NWC", "WIO", "NWC"))
    return out + b


def conv_encode(p, ids, *, pooled_only: bool = False):
    """Conv tower + MaxPool (+ hidden FC stack) -> shared features.

    ``pooled_only`` stops after the max-pool (the kernel module's seam).
    The mask follows the embedding dtype (lax.conv is strict about
    matching dtypes), so bf16-cast params run a bf16 tower. Each stage
    runs under a named scope (``embed``, ``conv{i}``, ``pool``, ``fc``)
    that a profile's op metadata carries."""
    with jax.named_scope("embed"):
        x = p["emb"][ids] * _mask(ids).astype(p["emb"].dtype)[..., None]
    for i, layer in enumerate(p["convs"]):
        with jax.named_scope(f"conv{i}"):
            x = jax.nn.relu(conv1d(x, layer["w"], layer["b"]))
    with jax.named_scope("pool"):
        x = x.max(axis=1)                        # MaxPool1D over sequence
    if pooled_only:
        return x
    with jax.named_scope("fc"):
        return fc_stack(p, x)


def conv_apply(p, ids, *, pooled_feats: bool = False):
    pooled = conv_encode(p, ids, pooled_only=True)
    out = fc_finish(p, pooled)
    return (out, pooled) if pooled_feats else out


# ------------------------------------------------- Transformer (beyond-paper)
# The paper's §6 future work #1: "Use more powerful models like
# Transformers to better the currently achieved accuracy figures".
def xformer_init(key, cfg, n_layers=2, n_heads=4,
                 heads: Optional[Sequence[str]] = None) -> Dict[str, Any]:
    d = cfg.embed_dim
    ks = jax.random.split(key, 2 + 5 * n_layers)
    p = {**embed_init(ks[0], cfg),
         "pos": _init(ks[1], (cfg.max_seq, d), scale=0.02),
         "blocks": []}
    for i in range(n_layers):
        o = 2 + 5 * i
        p["blocks"].append({
            "wqkv": _init(ks[o], (d, 3 * d)),
            "wo": _init(ks[o + 1], (d, d)),
            "ln1": jnp.ones((d,)), "ln2": jnp.ones((d,)),
            "w1": _init(ks[o + 2], (d, 4 * d)),
            "w2": _init(ks[o + 3], (4 * d, d)),
        })
    if heads:
        p["heads"] = heads_init(ks[-1], d, heads)
    else:
        p["head"] = {"w": _init(ks[-1], (d, 1)), "b": jnp.zeros((1,))}
    return p


def xformer_axes(cfg, heads: Optional[Sequence[str]] = None):
    blk = {"wqkv": ("embed", "ffn"), "wo": (None, "embed"),
           "ln1": (None,), "ln2": (None,),
           "w1": ("embed", "ffn"), "w2": ("ffn", "embed")}
    ax = {"emb": ("vocab", "embed"), "pos": (None, "embed"),
          "blocks": [blk, blk]}
    if heads:
        ax["heads"] = heads_axes(heads)
    else:
        ax["head"] = {"w": (None, None), "b": (None,)}
    return ax


def _ln(x, g):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + 1e-5) * g


def xformer_encode(p, ids):
    """Masked transformer stack -> mean-pooled features.

    Mask and attention bias follow the embedding dtype (bf16 still
    represents -1e30) so bf16-cast params stay bf16 end to end."""
    m = _mask(ids).astype(p["emb"].dtype)
    B, S = ids.shape
    d = p["emb"].shape[1]
    h = p["emb"][ids] + p["pos"][:S]
    H = 4  # fixed head count for the cost-model transformer
    dh = d // H
    # mask padded keys; cast keeps the bias in the embedding dtype
    # (bf16 represents -1e30) instead of promoting attention to f32
    neg = ((1.0 - m)[:, None, None, :] * -1e30).astype(m.dtype)
    for blk in p["blocks"]:
        x = _ln(h, blk["ln1"])
        qkv = x @ blk["wqkv"]
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(B, S, H, dh).transpose(0, 2, 1, 3)
        k = k.reshape(B, S, H, dh).transpose(0, 2, 1, 3)
        v = v.reshape(B, S, H, dh).transpose(0, 2, 1, 3)
        # python-float scale (weak-typed): an np.float64 scalar would
        # promote a bf16 tower back to f32 here
        a = jnp.einsum("bhqd,bhkd->bhqk", q, k) / float(np.sqrt(dh)) + neg
        w = jax.nn.softmax(a, axis=-1)
        o = jnp.einsum("bhqk,bhkd->bhqd", w, v).transpose(0, 2, 1, 3)
        h = h + o.reshape(B, S, d) @ blk["wo"]
        x = _ln(h, blk["ln2"])
        h = h + jax.nn.gelu(x @ blk["w1"]) @ blk["w2"]
    return (h * m[..., None]).sum(1) / jnp.maximum(
        m.sum(1, keepdims=True), 1.0)


def xformer_apply(p, ids):
    return _finish(p, xformer_encode(p, ids),
                   lambda f: scalar_head(p["head"], f))


MODELS = {
    "fc": (fc_init, fc_apply, fc_axes),
    "lstm": (lstm_init, lstm_apply, lstm_axes),
    "conv1d": (conv_init, conv_apply, conv_axes),
    "xformer": (xformer_init, xformer_apply, xformer_axes),
}

ENCODERS = {
    "fc": fc_encode,
    "lstm": lstm_encode,
    "conv1d": conv_encode,
    "xformer": xformer_encode,
}


def get_model(kind: str):
    if kind not in MODELS:
        raise KeyError(f"unknown model {kind!r}; one of {sorted(MODELS)}")
    return MODELS[kind]


def get_encoder(kind: str):
    if kind not in ENCODERS:
        raise KeyError(f"unknown model {kind!r}; one of {sorted(ENCODERS)}")
    return ENCODERS[kind]
