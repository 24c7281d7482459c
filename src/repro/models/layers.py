"""Core transformer layers, pure JAX, with logical sharding axes.

Conventions
-----------
* Params are nested dicts of jnp arrays; every init function has a matching
  ``*_axes`` function returning the same pytree of logical-axis tuples
  (consumed by :mod:`repro.runtime.sharding`).
* Params are stored fp32 (master weights); forward casts to ``cdt``
  (compute dtype, bf16 by default) — mixed-precision training.
* Attention is flash-style (lax.scan over key blocks, online softmax), so no
  S^2 buffer is ever materialized; this is what makes prefill_32k lowerable.
* Layer stacks are scanned (lax.scan over stacked params) for compact HLO
  and fast compiles.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

Params = Dict[str, Any]

DEFAULT_KBLK = 1024   # flash-attention key-block size
DEFAULT_QBLK = 512    # flash-attention query-block size (memory knob)


# ----------------------------------------------------------------- utilities
def _init(key, shape, scale=None, dtype=jnp.float32):
    fan_in = shape[0] if len(shape) > 1 else max(shape[0], 1)
    scale = scale if scale is not None else 1.0 / np.sqrt(fan_in)
    return jax.random.normal(key, shape, dtype) * jnp.asarray(scale, dtype)


def rms_norm(x, gamma, eps):
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)).astype(dt) * gamma.astype(dt)


def rope(x, positions, theta):
    """Rotary embedding. x: (..., S, H, D); positions: (..., S)."""
    d = x.shape[-1]
    half = d // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions.astype(jnp.float32)[..., :, None] * freqs
    # angles: (..., S, half) -> broadcast over heads
    cos = jnp.cos(angles)[..., :, None, :]
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ----------------------------------------------------------------- attention
def attention_init(key, cfg) -> Params:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    nq, nkv = cfg.n_heads, cfg.n_kv_heads
    ks = jax.random.split(key, 4)
    p = {
        "wq": _init(ks[0], (d, nq, hd)),
        "wk": _init(ks[1], (d, nkv, hd)),
        "wv": _init(ks[2], (d, nkv, hd)),
        "wo": _init(ks[3], (nq, hd, d), scale=1.0 / np.sqrt(nq * hd)),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((nq, hd))
        p["bk"] = jnp.zeros((nkv, hd))
        p["bv"] = jnp.zeros((nkv, hd))
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,))
        p["k_norm"] = jnp.ones((hd,))
    return p


def attention_axes(cfg):
    a = {
        "wq": ("embed", "heads", None),
        "wk": ("embed", "kv_heads", None),
        "wv": ("embed", "kv_heads", None),
        "wo": ("heads", None, "embed"),
    }
    if cfg.qkv_bias:
        a.update(bq=("heads", None), bk=("kv_heads", None),
                 bv=("kv_heads", None))
    if cfg.qk_norm:
        a.update(q_norm=(None,), k_norm=(None,))
    return a


def flash_attention(q, k, v, *, causal: bool, q_offset=0,
                    kblk: int = DEFAULT_KBLK, rules=None,
                    bias_decay: Optional[jnp.ndarray] = None):
    """Online-softmax attention, scanning over key blocks.

    q: (B, Sq, H, D); k, v: (B, Sk, H, D) (kv already repeated to H heads).
    q_offset: global position of q[0] (for causal masking of prefill chunks).
    Never materializes an (Sq, Sk) buffer larger than (Sq, kblk).
    """
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    kblk = min(kblk, Sk)
    n_blk = (Sk + kblk - 1) // kblk
    pad = n_blk * kblk - Sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    scale = 1.0 / np.sqrt(D)
    q_pos = q_offset + jnp.arange(Sq)

    kb = k.reshape(B, n_blk, kblk, H, D).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(B, n_blk, kblk, H, D).transpose(1, 0, 2, 3, 4)

    def constrain(x, *axes):
        return rules.constrain(x, *axes) if rules is not None else x

    def step(carry, inputs):
        m, lse, acc, blk_idx = carry
        kc, vc = inputs
        k_pos = blk_idx * kblk + jnp.arange(kblk)
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, kc,
                            preferred_element_type=jnp.float32) * scale
        logits = constrain(logits, "batch", "heads", "qseq", None)
        mask = (k_pos[None, :] <= q_pos[:, None]) if causal else \
            (k_pos[None, :] < Sk)
        mask = mask & (k_pos[None, :] < Sk)
        logits = jnp.where(mask[None, None], logits, -1e30)
        m_new = jnp.maximum(m, logits.max(axis=-1))
        p = jnp.exp(logits - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = lse * corr + p.sum(axis=-1)
        pv = jnp.einsum("bhqk,bkhd->bqhd", p.astype(vc.dtype), vc,
                        preferred_element_type=jnp.float32)
        acc_new = acc * corr.transpose(0, 2, 1)[..., None] + pv
        return (m_new, l_new, acc_new, blk_idx + 1), None

    m0 = jnp.full((B, H, Sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, H, Sq), jnp.float32)
    a0 = jnp.zeros((B, Sq, H, D), jnp.float32)
    (m, lse, acc, _), _ = jax.lax.scan(step, (m0, l0, a0, jnp.int32(0)),
                                       (kb, vb))
    out = acc / jnp.maximum(lse, 1e-30).transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def _qkv(p, x, cfg, positions, cdt):
    """Queries, keys and values (B, S, heads, D) of ``x``, with bias,
    qk-norm and rotary embedding where the config has them."""
    xc = x.astype(cdt)
    q = jnp.einsum("bsd,dhk->bshk", xc, p["wq"].astype(cdt))
    k = jnp.einsum("bsd,dhk->bshk", xc, p["wk"].astype(cdt))
    v = jnp.einsum("bsd,dhk->bshk", xc, p["wv"].astype(cdt))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(cdt)
        k = k + p["bk"].astype(cdt)
        v = v + p["bv"].astype(cdt)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    if cfg.rope:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    return q, k, v


def attention_kv(p, x, cfg, *, positions, cdt=jnp.bfloat16):
    """The keys and values a prefill of ``x`` writes into a decode
    cache, in its layout: {"k", "v": (B, n_kv, S, D)}."""
    _, k, v = _qkv(p, x, cfg, positions, cdt)
    return {"k": k.transpose(0, 2, 1, 3), "v": v.transpose(0, 2, 1, 3)}


def attention_apply(p, x, cfg, *, positions, rules=None, cdt=jnp.bfloat16,
                    cache: Optional[Dict] = None, cache_index=None):
    """GQA attention. If cache is given, single-token decode; else full seq.

    cache: {"k": (B, n_kv, S_cache, D), "v": same} sharded on cache_seq.
    Returns (out, new_cache).
    """
    B, S, d = x.shape
    nq, nkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    G = nq // nkv
    q, k, v = _qkv(p, x, cfg, positions, cdt)

    if cache is None:
        # train/prefill: repeat kv to full q heads, flash attention.
        # Head (tensor) parallelism when n_heads divides the model axis;
        # with rules.pad_attention_heads, odd head counts are zero-padded up
        # to the next multiple of the model axis (padded heads are sliced
        # away before the output projection — mathematically identity, +14%
        # flops for llava's 56->64, and it converts the expensive per-layer
        # CP<->TP re-sharding into clean head parallelism);
        # otherwise query-sequence context parallelism picks up that axis.
        kf = jnp.repeat(k, G, axis=2)
        vf = jnp.repeat(v, G, axis=2)
        n_eff = nq
        if rules is not None:
            heads_tp = rules.divisible(nq, "model")
            if not heads_tp and getattr(rules, "pad_attention_heads", False):
                m_sz = rules.axis_sizes.get("model", 1)
                n_eff = -(-nq // m_sz) * m_sz
                hp = n_eff - nq
                q = jnp.pad(q, ((0, 0), (0, 0), (0, hp), (0, 0)))
                kf = jnp.pad(kf, ((0, 0), (0, 0), (0, hp), (0, 0)))
                vf = jnp.pad(vf, ((0, 0), (0, 0), (0, hp), (0, 0)))
                heads_tp = True
            qs = None if heads_tp else "qseq"
            q = rules.constrain(q, "batch", qs, "heads", None)
            kf = rules.constrain(kf, "batch", None, "heads", None)
            vf = rules.constrain(vf, "batch", None, "heads", None)
        out = flash_attention(q, kf, vf, causal=True, rules=rules)
        if n_eff != nq:
            out = out[:, :, :nq]
        new_cache = None
    else:
        # decode: update seq-sharded cache at cache_index, grouped attention
        kc = cache["k"]  # (B, nkv, Sc, D)
        vc = cache["v"]
        k1 = k.transpose(0, 2, 1, 3)  # (B, nkv, 1, D)
        v1 = v.transpose(0, 2, 1, 3)
        kc = jax.lax.dynamic_update_slice(kc, k1.astype(kc.dtype),
                                          (0, 0, cache_index, 0))
        vc = jax.lax.dynamic_update_slice(vc, v1.astype(vc.dtype),
                                          (0, 0, cache_index, 0))
        if rules is not None:
            kc = rules.constrain(kc, "batch", "kv_heads", "cache_seq", None)
            vc = rules.constrain(vc, "batch", "kv_heads", "cache_seq", None)
        Sc = kc.shape[2]
        # -> B,nkv,G,S,D
        qg = q.reshape(B, S, nkv, G, hd).transpose(0, 2, 3, 1, 4)
        qg = qg.reshape(B, nkv, G * S, hd)
        logits = jnp.einsum("bhgk,bhsk->bhgs", qg, kc.astype(cdt),
                            preferred_element_type=jnp.float32)
        logits = logits / np.sqrt(hd)
        valid = jnp.arange(Sc) <= cache_index
        logits = jnp.where(valid[None, None, None], logits, -1e30)
        if rules is not None:
            logits = rules.constrain(logits, "batch", "kv_heads", None,
                                     "cache_seq")
        w = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bhgs,bhsk->bhgk", w.astype(cdt), vc.astype(cdt))
        out = out.reshape(B, nkv, G, S, hd).transpose(0, 3, 1, 2, 4)
        out = out.reshape(B, S, nq, hd)
        new_cache = {"k": kc, "v": vc}

    y = jnp.einsum("bshk,hkd->bsd", out.astype(cdt), p["wo"].astype(cdt))
    return y, new_cache


# ----------------------------------------------------------------- FFN
def ffn_init(key, d_model, d_ff, gated=True) -> Params:
    ks = jax.random.split(key, 3)
    p = {"w_up": _init(ks[0], (d_model, d_ff)),
         "w_down": _init(ks[1], (d_ff, d_model))}
    if gated:
        p["w_gate"] = _init(ks[2], (d_model, d_ff))
    return p


def ffn_axes(gated=True):
    a = {"w_up": ("embed", "ffn"), "w_down": ("ffn", "embed")}
    if gated:
        a["w_gate"] = ("embed", "ffn")
    return a


def ffn_apply(p, x, *, rules=None, cdt=jnp.bfloat16, gated=True):
    xc = x.astype(cdt)
    up = xc @ p["w_up"].astype(cdt)
    if gated:
        gate = jax.nn.silu(xc @ p["w_gate"].astype(cdt))
        h = gate * up
    else:
        h = jax.nn.gelu(up)
    if rules is not None:
        # ffn (tensor) parallelism owns the model axis here; the sequence
        # dim stays unsharded inside the FFN even under context parallelism
        h = rules.constrain(h, "batch", None, "ffn")
    return h @ p["w_down"].astype(cdt)


# ----------------------------------------------------------------- embedding
def embedding_init(key, vocab, d_model, pad_to=1) -> Params:
    vpad = ((vocab + pad_to - 1) // pad_to) * pad_to
    return {"table": _init(key, (vpad, d_model), scale=0.02)}


def embedding_axes():
    return {"table": ("vocab", "embed")}


def embed_apply(p, ids, cdt=jnp.bfloat16):
    return p["table"].astype(cdt)[ids]


def unembed_apply(p, x, cdt=jnp.bfloat16):
    return jnp.einsum("bsd,vd->bsv", x.astype(cdt), p["table"].astype(cdt))
