"""Full-model assembly for every assigned architecture family.

``init_params`` / ``param_axes`` / ``forward`` / ``init_cache`` /
``decode_forward`` dispatch on ``cfg.family``:

* dense | moe | vlm : token-embedding decoder LM, scanned uniform layers.
* hybrid (jamba)    : scanned periods of 1 attention + 7 mamba layers,
                      MoE where ``cfg.moe.is_moe(slot)`` (Jamba: odd
                      layers), a dense FFN on the others.
* ssm (xlstm)       : scanned (mLSTM, sLSTM) block pairs.
* audio (whisper)   : enc-dec; encoder over stubbed frame embeddings.

All inits are pure (usable under jax.eval_shape for the no-allocation
dry-run). Layer stacks scan over stacked params (leading "stack" axis).
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models import moe as M
from repro.models import mamba as MB
from repro.models import xlstm as X

VOCAB_PAD = 128


def padded_vocab(cfg) -> int:
    return ((cfg.vocab + VOCAB_PAD - 1) // VOCAB_PAD) * VOCAB_PAD


def _stack_init(key, n, fn):
    return jax.vmap(fn)(jax.random.split(key, n))


def _stack_axes(n, axes):
    return jax.tree.map(lambda a: ("stack",) + a, axes,
                        is_leaf=lambda x: isinstance(x, tuple) and all(
                            isinstance(e, (str, type(None))) for e in x))


# =========================================================== uniform decoder
def _layer_init(key, cfg, gated=True):
    k1, k2 = jax.random.split(key)
    p = {"attn": L.attention_init(k1, cfg),
         "ln1": jnp.ones((cfg.d_model,)), "ln2": jnp.ones((cfg.d_model,))}
    if cfg.moe is not None and cfg.moe.moe_every == 1:
        p["moe"] = M.moe_init(k2, cfg)
    else:
        p["ffn"] = L.ffn_init(k2, cfg.d_model, cfg.d_ff, gated=gated)
    return p


def _layer_axes(cfg, gated=True):
    a = {"attn": L.attention_axes(cfg), "ln1": (None,), "ln2": (None,)}
    if cfg.moe is not None and cfg.moe.moe_every == 1:
        a["moe"] = M.moe_axes(cfg)
    else:
        a["ffn"] = L.ffn_axes(gated=gated)
    return a


def _layer_apply(p, h, cfg, *, positions, rules, cdt, cache=None,
                 cache_index=None):
    attn_in = L.rms_norm(h, p["ln1"], cfg.norm_eps)
    a, new_cache = L.attention_apply(p["attn"], attn_in, cfg,
                                     positions=positions, rules=rules,
                                     cdt=cdt, cache=cache,
                                     cache_index=cache_index)
    h = h + a.astype(h.dtype)
    ffn_in = L.rms_norm(h, p["ln2"], cfg.norm_eps)
    if "moe" in p:
        f, aux = M.moe_apply(p["moe"], ffn_in, cfg, rules=rules, cdt=cdt)
    else:
        f = L.ffn_apply(p["ffn"], ffn_in, rules=rules, cdt=cdt)
        aux = jnp.zeros((), jnp.float32)
    return h + f.astype(h.dtype), new_cache, aux


# =========================================================== hybrid (jamba)
def _period_init(key, cfg):
    hb = cfg.hybrid
    ks = jax.random.split(key, 5)
    n_mamba = hb.period - 1
    n_moe = sum(1 for s in range(hb.period) if cfg.moe.is_moe(s))
    n_dense = hb.period - n_moe
    return {
        "attn": L.attention_init(ks[0], cfg),
        "mamba": _stack_init(ks[1], n_mamba, lambda k: MB.mamba_init(k, cfg)),
        "moe": _stack_init(ks[2], n_moe, lambda k: M.moe_init(k, cfg)),
        "ffn": _stack_init(ks[3], n_dense,
                           lambda k: L.ffn_init(k, cfg.d_model, cfg.d_ff)),
        "ln1": jnp.ones((hb.period, cfg.d_model)),
        "ln2": jnp.ones((hb.period, cfg.d_model)),
    }


def _period_axes(cfg):
    return {
        "attn": L.attention_axes(cfg),
        "mamba": _stack_axes(0, MB.mamba_axes(cfg)),
        "moe": _stack_axes(0, M.moe_axes(cfg)),
        "ffn": _stack_axes(0, L.ffn_axes()),
        "ln1": (None, None), "ln2": (None, None),
    }


def _period_apply(p, h, cfg, *, positions, rules, cdt, caches=None,
                  cache_index=None):
    """One period: slots 0..period-1; attention at hb.attn_index."""
    hb = cfg.hybrid
    mamba_i = moe_i = ffn_i = 0
    new_attn_cache, new_mamba_states = None, []
    aux_total = jnp.zeros((), jnp.float32)
    for slot in range(hb.period):
        mix_in = L.rms_norm(h, p["ln1"][slot], cfg.norm_eps)
        if slot == hb.attn_index:
            cache = caches["attn"] if caches is not None else None
            a, new_attn_cache = L.attention_apply(
                p["attn"], mix_in, cfg, positions=positions, rules=rules,
                cdt=cdt, cache=cache, cache_index=cache_index)
        else:
            mp = jax.tree.map(lambda x: x[mamba_i], p["mamba"])
            st = (jax.tree.map(lambda x: x[mamba_i], caches["mamba"])
                  if caches is not None else None)
            a, new_st = MB.mamba_apply(mp, mix_in, cfg, rules=rules,
                                       cdt=cdt, state=st)
            if caches is not None:
                new_mamba_states.append(new_st)
            mamba_i += 1
        h = h + a.astype(h.dtype)
        ffn_in = L.rms_norm(h, p["ln2"][slot], cfg.norm_eps)
        if cfg.moe.is_moe(slot):
            ep = jax.tree.map(lambda x: x[moe_i], p["moe"])
            f, aux = M.moe_apply(ep, ffn_in, cfg, rules=rules, cdt=cdt)
            aux_total = aux_total + aux
            moe_i += 1
        else:
            fp = jax.tree.map(lambda x: x[ffn_i], p["ffn"])
            f = L.ffn_apply(fp, ffn_in, rules=rules, cdt=cdt)
            ffn_i += 1
        h = h + f.astype(h.dtype)
    new_caches = None
    if caches is not None:
        new_caches = {
            "attn": new_attn_cache,
            "mamba": jax.tree.map(lambda *xs: jnp.stack(xs),
                                  *new_mamba_states),
        }
    return h, new_caches, aux_total


# =========================================================== whisper enc-dec
def _enc_layer_init(key, cfg):
    k1, k2 = jax.random.split(key)
    return {"attn": L.attention_init(k1, cfg),
            "ffn": L.ffn_init(k2, cfg.d_model, cfg.d_ff, gated=False),
            "ln1": jnp.ones((cfg.d_model,)), "ln2": jnp.ones((cfg.d_model,))}


def _enc_layer_apply(p, h, cfg, *, rules, cdt):
    """Bidirectional attention (no causal mask, no rope — learned pos)."""
    x = L.rms_norm(h, p["ln1"], cfg.norm_eps).astype(cdt)
    q = jnp.einsum("bsd,dhk->bshk", x, p["attn"]["wq"].astype(cdt))
    k = jnp.einsum("bsd,dhk->bshk", x, p["attn"]["wk"].astype(cdt))
    v = jnp.einsum("bsd,dhk->bshk", x, p["attn"]["wv"].astype(cdt))
    o = L.flash_attention(q, k, v, causal=False, rules=rules)
    a = jnp.einsum("bshk,hkd->bsd", o, p["attn"]["wo"].astype(cdt))
    h = h + a.astype(h.dtype)
    f = L.ffn_apply(p["ffn"], L.rms_norm(h, p["ln2"], cfg.norm_eps),
                    rules=rules, cdt=cdt, gated=False)
    return h + f.astype(h.dtype)


def _dec_layer_init(key, cfg):
    k1, k2, k3 = jax.random.split(key, 3)
    return {"attn": L.attention_init(k1, cfg),
            "xattn": L.attention_init(k2, cfg),
            "ffn": L.ffn_init(k3, cfg.d_model, cfg.d_ff, gated=False),
            "ln1": jnp.ones((cfg.d_model,)), "lnx": jnp.ones((cfg.d_model,)),
            "ln2": jnp.ones((cfg.d_model,))}


def _cross_attend(p, x, enc_kv, cfg, rules, cdt):
    q = jnp.einsum("bsd,dhk->bshk", x.astype(cdt), p["wq"].astype(cdt))
    o = L.flash_attention(q, enc_kv["k"].astype(cdt),
                          enc_kv["v"].astype(cdt), causal=False, rules=rules)
    return jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(cdt))


def _dec_layer_apply(p, h, cfg, *, positions, enc_kv, rules, cdt,
                     cache=None, cache_index=None):
    a_in = L.rms_norm(h, p["ln1"], cfg.norm_eps)
    a, new_cache = L.attention_apply(p["attn"], a_in, cfg,
                                     positions=positions, rules=rules,
                                     cdt=cdt, cache=cache,
                                     cache_index=cache_index)
    h = h + a.astype(h.dtype)
    x_in = L.rms_norm(h, p["lnx"], cfg.norm_eps)
    xa = _cross_attend(p["xattn"], x_in, enc_kv, cfg, rules, cdt)
    h = h + xa.astype(h.dtype)
    f = L.ffn_apply(p["ffn"], L.rms_norm(h, p["ln2"], cfg.norm_eps),
                    rules=rules, cdt=cdt, gated=False)
    return h + f.astype(h.dtype), new_cache


# ================================================================= top level
def init_params(key, cfg) -> Dict[str, Any]:
    ks = jax.random.split(key, 8)
    vp = padded_vocab(cfg)
    p: Dict[str, Any] = {
        "embed": L.embedding_init(ks[0], cfg.vocab, cfg.d_model,
                                  pad_to=VOCAB_PAD),
        "final_norm": jnp.ones((cfg.d_model,)),
    }
    if not cfg.tie_embeddings:
        p["unembed"] = {"table": L._init(ks[1], (vp, cfg.d_model),
                                         scale=0.02)}
    fam = cfg.family
    if fam == "ssm":
        n_pairs = max(cfg.n_layers // 2, 1)
        p["pairs"] = _stack_init(ks[2], n_pairs, lambda k: {
            "mlstm": X.mlstm_init(k, cfg),
            "slstm": X.slstm_init(jax.random.fold_in(k, 1), cfg)})
    elif fam == "hybrid":
        n_periods = cfg.n_layers // cfg.hybrid.period
        p["periods"] = _stack_init(ks[2], n_periods,
                                   lambda k: _period_init(k, cfg))
    elif fam == "audio":
        p["enc_pos"] = L._init(ks[3], (cfg.encoder_seq, cfg.d_model),
                               scale=0.02)
        p["dec_pos"] = L._init(ks[4], (32768, cfg.d_model), scale=0.02)
        p["enc_layers"] = _stack_init(ks[2], cfg.n_encoder_layers,
                                      lambda k: _enc_layer_init(k, cfg))
        p["dec_layers"] = _stack_init(ks[5], cfg.n_layers,
                                      lambda k: _dec_layer_init(k, cfg))
        p["enc_norm"] = jnp.ones((cfg.d_model,))
    else:  # dense | moe | vlm
        gated = True
        p["layers"] = _stack_init(ks[2], cfg.n_layers,
                                  lambda k: _layer_init(k, cfg, gated))
    return p


def param_axes(cfg) -> Dict[str, Any]:
    a: Dict[str, Any] = {
        "embed": L.embedding_axes(),
        "final_norm": (None,),
    }
    if not cfg.tie_embeddings:
        a["unembed"] = {"table": ("vocab", "embed")}
    fam = cfg.family
    if fam == "ssm":
        a["pairs"] = _stack_axes(0, {"mlstm": X.mlstm_axes(cfg),
                                     "slstm": X.slstm_axes(cfg)})
    elif fam == "hybrid":
        a["periods"] = _stack_axes(0, _period_axes(cfg))
    elif fam == "audio":
        a["enc_pos"] = (None, "embed")
        a["dec_pos"] = (None, "embed")
        a["enc_layers"] = _stack_axes(0, {
            "attn": L.attention_axes(cfg), "ffn": L.ffn_axes(gated=False),
            "ln1": (None,), "ln2": (None,)})
        a["dec_layers"] = _stack_axes(0, {
            "attn": L.attention_axes(cfg), "xattn": L.attention_axes(cfg),
            "ffn": L.ffn_axes(gated=False),
            "ln1": (None,), "lnx": (None,), "ln2": (None,)})
        a["enc_norm"] = (None,)
    else:
        a["layers"] = _stack_axes(0, _layer_axes(cfg))
    return a


def _embed_tokens(p, cfg, batch, cdt, rules):
    h = L.embed_apply(p["embed"], batch["tokens"], cdt=cdt)
    if cfg.frontend == "vision" and "patch_embeds" in batch:
        pe = batch["patch_embeds"].astype(cdt)
        P = pe.shape[1]
        h = jnp.concatenate([pe, h[:, P:]], axis=1)
    if rules is not None:
        h = rules.constrain(h, "batch", "qseq", "embed")
    return h


def _run_encoder(p, cfg, frame_embeds, rules, cdt):
    h = frame_embeds.astype(cdt) + p["enc_pos"].astype(cdt)

    def body(hh, lp):
        return _enc_layer_apply(lp, hh, cfg, rules=rules, cdt=cdt), None

    h, _ = jax.lax.scan(jax.checkpoint(body), h, p["enc_layers"])
    return L.rms_norm(h, p["enc_norm"], cfg.norm_eps)


def _enc_kv(p_layer, enc_out, cfg, cdt):
    G = cfg.n_heads // cfg.n_kv_heads
    k = jnp.einsum("bsd,dhk->bshk", enc_out.astype(cdt),
                   p_layer["xattn"]["wk"].astype(cdt))
    v = jnp.einsum("bsd,dhk->bshk", enc_out.astype(cdt),
                   p_layer["xattn"]["wv"].astype(cdt))
    return {"k": jnp.repeat(k, G, axis=2), "v": jnp.repeat(v, G, axis=2)}


def forward(params, cfg, batch, *, rules=None, cdt=jnp.bfloat16,
            remat=True, unembed=True) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Training/prefill forward. Returns (logits, aux_loss) — or, with
    unembed=False, (final hidden states, aux_loss) so the caller can fuse
    the unembedding into a chunked loss (never materializing full logits)."""
    B, S = batch["tokens"].shape
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    aux = jnp.zeros((), jnp.float32)
    fam = cfg.family

    if fam == "audio":
        enc_out = _run_encoder(p=params, cfg=cfg,
                               frame_embeds=batch["frame_embeds"],
                               rules=rules, cdt=cdt)
        h = L.embed_apply(params["embed"], batch["tokens"], cdt=cdt)
        h = h + params["dec_pos"][:S].astype(cdt)

        def dbody(hh, lp):
            ekv = _enc_kv(lp, enc_out, cfg, cdt)
            out, _ = _dec_layer_apply(lp, hh, cfg, positions=positions,
                                      enc_kv=ekv, rules=rules, cdt=cdt)
            return out, None

        dbody = jax.checkpoint(dbody) if remat else dbody
        h, _ = jax.lax.scan(dbody, h, params["dec_layers"])
    elif fam == "ssm":
        h = _embed_tokens(params, cfg, batch, cdt, rules)

        def pbody(hh, pp):
            hh, _ = X.mlstm_block_apply(pp["mlstm"], hh, cfg, rules=rules,
                                        cdt=cdt)
            hh, _ = X.slstm_block_apply(pp["slstm"], hh, cfg, rules=rules,
                                        cdt=cdt)
            return hh, None

        pbody = jax.checkpoint(pbody) if remat else pbody
        h, _ = jax.lax.scan(pbody, h, params["pairs"])
    elif fam == "hybrid":
        h = _embed_tokens(params, cfg, batch, cdt, rules)

        def hbody(hh, pp):
            out, _, aux_p = _period_apply(pp, hh, cfg, positions=positions,
                                          rules=rules, cdt=cdt)
            return out, aux_p

        hbody = jax.checkpoint(hbody) if remat else hbody
        h, auxs = jax.lax.scan(hbody, h, params["periods"])
        aux = aux + auxs.sum()
    else:
        h = _embed_tokens(params, cfg, batch, cdt, rules)

        def body(hh, lp):
            out, _, aux_l = _layer_apply(lp, hh, cfg, positions=positions,
                                         rules=rules, cdt=cdt)
            return out, aux_l

        body = jax.checkpoint(body) if remat else body
        h, auxs = jax.lax.scan(body, h, params["layers"])
        aux = aux + auxs.sum()

    h = L.rms_norm(h, params["final_norm"], cfg.norm_eps)
    if not unembed:
        return h, aux
    table = params["embed"]["table"] if cfg.tie_embeddings else \
        params["unembed"]["table"]
    logits = jnp.einsum("bsd,vd->bsv", h.astype(cdt), table.astype(cdt))
    if rules is not None:
        logits = rules.constrain(logits, "batch", "qseq", "vocab")
    return logits, aux


def unembed_table(params, cfg):
    return params["embed"]["table"] if cfg.tie_embeddings else \
        params["unembed"]["table"]


# ------------------------------------------------------------------- decode
def init_cache(cfg, batch: int, max_seq: int, *, kv_dtype=jnp.bfloat16):
    """Decode-state pytree (KV caches / recurrent states)."""
    nkv, hd = cfg.n_kv_heads, cfg.resolved_head_dim

    def kvc():
        z = jnp.zeros((batch, nkv, max_seq, hd), kv_dtype)
        return {"k": z, "v": jnp.copy(z)}

    def stack(n, tree):
        return jax.tree.map(
            lambda x: jnp.broadcast_to(x, (n,) + x.shape).copy(), tree)

    fam = cfg.family
    if fam == "ssm":
        n_pairs = max(cfg.n_layers // 2, 1)
        return stack(n_pairs, {"mlstm": X.mlstm_init_state(cfg, batch),
                               "slstm": X.slstm_init_state(cfg, batch)})
    if fam == "hybrid":
        n_periods = cfg.n_layers // cfg.hybrid.period
        one = {"attn": kvc(),
               "mamba": stack(cfg.hybrid.period - 1,
                              MB.mamba_init_state(cfg, batch))}
        return stack(n_periods, one)
    if fam == "audio":
        return {"self": stack(cfg.n_layers, kvc()),
                "enc_out": jnp.zeros((batch, cfg.encoder_seq, cfg.d_model),
                                     jnp.bfloat16)}
    return stack(cfg.n_layers, kvc())


def cache_axes(cfg):
    """Logical sharding axes matching init_cache's pytree."""
    kv_axes = {"k": ("stack", "batch", "kv_heads", "cache_seq", None),
               "v": ("stack", "batch", "kv_heads", "cache_seq", None)}
    fam = cfg.family
    if fam == "ssm":
        return {
            "mlstm": {
                "conv": ("stack", "batch", None, "ffn"),
                "cell": {"C": ("stack", "batch", "heads", None, None),
                         "n": ("stack", "batch", "heads", None),
                         "m": ("stack", "batch", "heads")}},
            "slstm": {k: ("stack", "batch", None)
                      for k in ("c", "n", "h", "m")},
        }
    if fam == "hybrid":
        return {
            "attn": kv_axes,
            "mamba": {"conv": ("stack", "stack2", "batch", None, "ffn"),
                      "ssm": ("stack", "stack2", "batch", "ffn", None)},
        }
    if fam == "audio":
        return {"self": kv_axes, "enc_out": ("batch", None, "embed")}
    return kv_axes


def decode_forward(params, cfg, tokens, cache, index, *, rules=None,
                   cdt=jnp.bfloat16):
    """One decode step. tokens: (B, 1) int32; index: scalar position.
    Returns (logits (B, vocab_padded), new_cache)."""
    B = tokens.shape[0]
    positions = jnp.broadcast_to(index, (B, 1))
    fam = cfg.family
    h = L.embed_apply(params["embed"], tokens, cdt=cdt)

    if fam == "audio":
        h = h + jax.lax.dynamic_slice_in_dim(
            params["dec_pos"], index, 1).astype(cdt)
        enc_out = cache["enc_out"]

        def dbody(hh, xs):
            lp, c = xs
            ekv = _enc_kv(lp, enc_out, cfg, cdt)
            out, nc = _dec_layer_apply(lp, hh, cfg, positions=positions,
                                       enc_kv=ekv, rules=rules, cdt=cdt,
                                       cache=c, cache_index=index)
            return out, nc

        h, new_self = jax.lax.scan(dbody, h, (params["dec_layers"],
                                              cache["self"]))
        new_cache = {"self": new_self, "enc_out": enc_out}
    elif fam == "ssm":
        def pbody(hh, xs):
            pp, st = xs
            hh, s1 = X.mlstm_block_apply(pp["mlstm"], hh, cfg, rules=rules,
                                         cdt=cdt, state=st["mlstm"])
            hh, s2 = X.slstm_block_apply(pp["slstm"], hh, cfg, rules=rules,
                                         cdt=cdt, state=st["slstm"])
            return hh, {"mlstm": s1, "slstm": s2}

        h, new_cache = jax.lax.scan(pbody, h, (params["pairs"], cache))
    elif fam == "hybrid":
        def hbody(hh, xs):
            pp, c = xs
            out, nc, _ = _period_apply(pp, hh, cfg, positions=positions,
                                       rules=rules, cdt=cdt, caches=c,
                                       cache_index=index)
            return out, nc

        h, new_cache = jax.lax.scan(hbody, h, (params["periods"], cache))
    else:
        def body(hh, xs):
            lp, c = xs
            out, nc, _ = _layer_apply(lp, hh, cfg, positions=positions,
                                      rules=rules, cdt=cdt, cache=c,
                                      cache_index=index)
            return out, nc

        h, new_cache = jax.lax.scan(body, h, (params["layers"], cache))

    h = L.rms_norm(h, params["final_norm"], cfg.norm_eps)
    table = params["embed"]["table"] if cfg.tie_embeddings else \
        params["unembed"]["table"]
    logits = jnp.einsum("bsd,vd->bsv", h.astype(cdt), table.astype(cdt))
    if rules is not None:
        logits = rules.constrain(logits, "batch", None, "vocab")
    return logits[:, 0], new_cache
