"""Token-choice top-k MoE with chunked capacity-based dispatch.

Dispatch/combine are dense one-hot einsums (GSPMD-friendly: no data-dependent
shapes), applied per sequence chunk so the (tokens, experts, capacity)
dispatch tensor stays small even at 32k sequence length. Experts shard over
the ``model`` mesh axis (expert parallelism); the dispatch einsum lowers to
an all-to-all-like collective under GSPMD.

Active-FLOPs accounting: per token, top_k experts * capacity_factor slack,
matching the 6*N_active*D convention used in the roofline.

``capacity_factor=None`` is dropless: each expert gets one slot per token
of the chunk, and since a token picks an expert at most once among its
top-k, no expert can overflow. ``renormalize=False`` keeps the softmax
weights of the top-k experts as they are, as Jamba's router does;
Mixtral-style routers renormalize them.

Expert parallelism: a layer whose expert weights hold ``n_held`` of the
``n_experts`` (their leading axis, ``first_expert`` onward) still routes
every token over all of them with the full router, and computes only
its own experts' part of the output; the shares of the chips that hold
the other experts add up to the whole layer.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.layers import _init

MOE_CHUNK = 1024  # sequence chunk for dispatch (memory knob)


def moe_init(key, cfg) -> Dict[str, Any]:
    m = cfg.moe
    d, f, e = cfg.d_model, m.d_ff_expert, m.n_experts
    ks = jax.random.split(key, 4)
    return {
        "router": _init(ks[0], (d, e), scale=0.02),
        "w_gate": _init(ks[1], (e, d, f)),
        "w_up": _init(ks[2], (e, d, f)),
        "w_down": _init(ks[3], (e, f, d), scale=1.0 / np.sqrt(f)),
    }


def moe_axes(cfg):
    return {
        "router": ("embed", None),
        "w_gate": ("experts", "embed", "ffn"),
        "w_up": ("experts", "embed", "ffn"),
        "w_down": ("experts", "ffn", "embed"),
    }


def _capacity(chunk_tokens: int, cfg) -> int:
    m = cfg.moe
    if m.capacity_factor is None:
        return chunk_tokens
    cap = int(np.ceil(
        chunk_tokens * m.top_k * m.capacity_factor / m.n_experts))
    return max(cap, m.top_k)


def moe_apply(p, x, cfg, *, rules=None, cdt=jnp.bfloat16,
              first_expert: int = 0):
    """x: (B, S, D) -> (out (B, S, D), aux_loss scalar).

    ``p`` holds the experts ``first_expert`` .. ``first_expert + n_held
    - 1`` (the leading axis of its expert weights); ``out`` is their
    part of the layer's output."""
    B, S, D = x.shape
    m = cfg.moe
    E, K = m.n_experts, m.top_k
    Eh = p["w_gate"].shape[0]
    held = slice(first_expert, first_expert + Eh)
    chunk = min(MOE_CHUNK, S)
    n_chunks = S // chunk if S % chunk == 0 else -(-S // chunk)
    pad = n_chunks * chunk - S
    xp = jnp.pad(x, ((0, 0), (0, pad), (0, 0))) if pad else x
    xc = xp.reshape(B, n_chunks, chunk, D).transpose(1, 0, 2, 3)
    cap = _capacity(chunk, cfg)

    def one_chunk(xch):
        # xch: (B, c, D)
        h = xch.astype(cdt)
        logits = (h @ p["router"].astype(cdt)).astype(jnp.float32)  # B,c,E
        probs = jax.nn.softmax(logits, axis=-1)
        topv, topi = jax.lax.top_k(probs, K)                         # B,c,K
        if m.renormalize:
            topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)
        onehot_all = jax.nn.one_hot(topi, E, dtype=jnp.float32)      # B,c,K,E
        onehot = onehot_all[..., held]                               # B,c,K,Eh
        # slot position of each (token, k) within its expert, via cumsum
        flat = onehot.reshape(B, chunk * K, Eh)
        pos = jnp.cumsum(flat, axis=1) - flat                        # B,cK,Eh
        pos = pos.reshape(B, chunk, K, Eh)
        slot = (pos * onehot).sum(-1)                                # B,c,K
        keep = slot < cap
        slot_oh = jax.nn.one_hot(jnp.where(keep, slot, cap), cap + 1,
                                 dtype=jnp.float32)[..., :cap]  # B,c,K,cap
        disp = jnp.einsum("bcke,bckp->bcep", onehot, slot_oh)  # B,c,E,cap
        comb = jnp.einsum("bcke,bckp,bck->bcep", onehot, slot_oh,
                          topv.astype(jnp.float32))
        # dispatch tokens to expert slots
        xin = jnp.einsum("bcep,bcd->ebpd", disp.astype(cdt), h)  # E,B,cap,D
        if rules is not None:
            xin = rules.constrain(xin, "experts", "batch", None, None)
        gate = jax.nn.silu(jnp.einsum("ebpd,edf->ebpf", xin,
                                      p["w_gate"].astype(cdt)))
        up = jnp.einsum("ebpd,edf->ebpf", xin, p["w_up"].astype(cdt))
        eout = jnp.einsum("ebpf,efd->ebpd", gate * up,
                          p["w_down"].astype(cdt))
        if rules is not None:
            eout = rules.constrain(eout, "experts", "batch", None, None)
        out = jnp.einsum("bcep,ebpd->bcd", comb.astype(cdt), eout)   # B,c,D
        # load-balance aux (Switch-style): mean prob * mean assigned fraction
        me = probs.mean(axis=(0, 1))                                 # E
        ce = onehot_all.mean(axis=(0, 1, 2)) * K                     # E
        aux = (me * ce).sum() * E
        return out, aux

    outs, auxs = jax.lax.map(one_chunk, xc)
    out = outs.transpose(1, 0, 2, 3).reshape(B, n_chunks * chunk, D)
    if pad:
        out = out[:, :S]
    return out, auxs.mean() * cfg.moe.router_aux_weight
