"""Selective SSM (Mamba) block for the Jamba hybrid architecture.

Training/prefill uses a chunked scan: ``lax.scan`` over sequence chunks with
a ``lax.associative_scan`` inside each chunk, so the (B, S, d_inner, d_state)
tensor never materializes at full sequence length. Decode is the O(1)
recurrent update. d_inner shards over the ``model`` mesh axis ("ffn" logical
axis) — conv/gating are elementwise over d_inner, and the B/C projections
reduce over the sharded dim (GSPMD inserts the small all-reduces).

With ``cfg.hybrid.ssm_norm`` (Jamba) dt, B and C each pass through an
RMSNorm of their own (weights ``dt_norm``, ``b_norm``, ``c_norm``, eps
``cfg.norm_eps``) after ``x_proj``, as transformers' ``JambaMambaMixer``
does. A prefill asked for its state (``return_state=True``) hands back
the conv window and the SSM state after its last position, which a
decode step continues from.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.layers import _init, rms_norm

MAMBA_CHUNK = 256


def mamba_init(key, cfg) -> Dict[str, Any]:
    h = cfg.hybrid
    d = cfg.d_model
    di = h.expand * d
    ks = jax.random.split(key, 6)
    dt_rank = h.resolved_dt_rank(d)
    p = {
        "in_proj": _init(ks[0], (d, 2 * di)),
        "conv_w": _init(ks[1], (h.d_conv, di), scale=0.5),
        "conv_b": jnp.zeros((di,)),
        "x_proj": _init(ks[2], (di, dt_rank + 2 * h.d_state)),
        "dt_proj": _init(ks[3], (dt_rank, di), scale=dt_rank ** -0.5),
        "dt_bias": jnp.log(jnp.expm1(
            jnp.exp(jax.random.uniform(ks[4], (di,),
                                       minval=np.log(1e-3),
                                       maxval=np.log(1e-1))))),
        "A_log": jnp.log(jnp.tile(jnp.arange(1, h.d_state + 1,
                                             dtype=jnp.float32), (di, 1))),
        "D": jnp.ones((di,)),
        "out_proj": _init(ks[5], (di, d)),
    }
    if h.ssm_norm:
        p.update(dt_norm=jnp.ones((dt_rank,)), b_norm=jnp.ones((h.d_state,)),
                 c_norm=jnp.ones((h.d_state,)))
    return p


def mamba_axes(cfg):
    a = {
        "in_proj": ("embed", "ffn"),
        "conv_w": (None, "ffn"),
        "conv_b": ("ffn",),
        "x_proj": ("ffn", None),
        "dt_proj": (None, "ffn"),
        "dt_bias": ("ffn",),
        "A_log": ("ffn", None),
        "D": ("ffn",),
        "out_proj": ("ffn", "embed"),
    }
    if cfg.hybrid.ssm_norm:
        a.update(dt_norm=(None,), b_norm=(None,), c_norm=(None,))
    return a


def _causal_conv(x, w, b, state: Optional[jnp.ndarray] = None):
    """Depthwise causal conv. x: (B, S, di); w: (k, di).
    state: (B, k-1, di)."""
    k = w.shape[0]
    if state is None:
        xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    else:
        xp = jnp.concatenate([state.astype(x.dtype), x], axis=1)
    out = sum(xp[:, i:i + x.shape[1]] * w[i] for i in range(k))
    new_state = xp[:, -(k - 1):] if k > 1 else None
    return out + b, new_state


def _ssm_params(p, x, cfg, cdt):
    """x: (B, S, di) -> dt (B,S,di), B_ (B,S,N), C (B,S,N), A (di,N)."""
    h = cfg.hybrid
    dt_rank = p["dt_proj"].shape[0]
    proj = x @ p["x_proj"].astype(cdt)
    dt_in, Bm, Cm = jnp.split(proj, [dt_rank, dt_rank + h.d_state], axis=-1)
    if h.ssm_norm:
        dt_in = rms_norm(dt_in, p["dt_norm"], cfg.norm_eps)
        Bm = rms_norm(Bm, p["b_norm"], cfg.norm_eps)
        Cm = rms_norm(Cm, p["c_norm"], cfg.norm_eps)
    dt = jax.nn.softplus((dt_in @ p["dt_proj"].astype(cdt)).astype(jnp.float32)
                         + p["dt_bias"])
    A = -jnp.exp(p["A_log"])  # (di, N)
    return dt, Bm.astype(jnp.float32), Cm.astype(jnp.float32), A


def mamba_apply(p, x, cfg, *, rules=None, cdt=jnp.bfloat16,
                state: Optional[Dict] = None, return_state: bool = False):
    """x: (B, S, D). state (decode): {"conv": (B,k-1,di), "ssm": (B,di,N)}.

    Returns (out, new_state); a prefill's new_state is None unless
    ``return_state``."""
    B, S, D = x.shape
    h = cfg.hybrid
    di = h.expand * D
    xc = x.astype(cdt)
    xz = xc @ p["in_proj"].astype(cdt)
    xin, z = jnp.split(xz, 2, axis=-1)
    if rules is not None:
        xin = rules.constrain(xin, "batch", None, "ffn")
        z = rules.constrain(z, "batch", None, "ffn")

    if state is not None:
        xin, conv_state = _causal_conv(xin, p["conv_w"].astype(cdt),
                                       p["conv_b"].astype(cdt),
                                       state["conv"])
        xin = jax.nn.silu(xin)
        dt, Bm, Cm, A = _ssm_params(p, xin, cfg, cdt)
        # recurrent update: s' = exp(dt*A)*s + dt*B*x
        dA = jnp.exp(dt[:, 0, :, None] * A[None])            # B,di,N
        dBx = dt[:, 0, :, None] * Bm[:, 0, None, :] * \
            xin[:, 0, :, None].astype(jnp.float32)
        s = state["ssm"] * dA + dBx
        y = (s * Cm[:, 0, None, :]).sum(-1)                  # B,di
        y = y + p["D"] * xin[:, 0].astype(jnp.float32)
        y = (y.astype(cdt) * jax.nn.silu(z[:, 0]))[:, None]  # B,1,di
        out = y @ p["out_proj"].astype(cdt)
        return out, {"conv": conv_state, "ssm": s}

    # train/prefill: chunked associative scan
    k = p["conv_w"].shape[0]
    conv_state = jnp.pad(xin, ((0, 0), (max(k - 1 - S, 0), 0), (0, 0))
                         )[:, -(k - 1):]
    xin, _ = _causal_conv(xin, p["conv_w"].astype(cdt),
                          p["conv_b"].astype(cdt))
    xin = jax.nn.silu(xin)
    dt, Bm, Cm, A = _ssm_params(p, xin, cfg, cdt)

    chunk = min(MAMBA_CHUNK, S)
    n_chunks = -(-S // chunk)
    pad = n_chunks * chunk - S
    if pad:
        xin = jnp.pad(xin, ((0, 0), (0, pad), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0)))

    def rsh(t):
        return t.reshape(B, n_chunks, chunk, -1).transpose(1, 0, 2, 3)

    xch, dtch, Bch, Cch = (rsh(xin.astype(jnp.float32)), rsh(dt),
                           rsh(Bm), rsh(Cm))

    def chunk_step(s0, inp):
        xc_, dt_, B_, C_ = inp                       # (B, c, di|N)
        dA = jnp.exp(dt_[..., None] * A)             # B,c,di,N
        dBx = dt_[..., None] * B_[:, :, None, :] * xc_[..., None]

        def combine(a, b):
            (a1, b1), (a2, b2) = a, b
            return a1 * a2, b1 * a2 + b2

        aA, aB = jax.lax.associative_scan(combine, (dA, dBx), axis=1)
        s = aA * s0[:, None] + aB                    # B,c,di,N
        y = (s * C_[:, :, None, :]).sum(-1)          # B,c,di
        return s[:, -1], y

    # padded positions have dt = 0: exp(0 * A) = 1 and no input, so the
    # state after the last chunk is the state after position S - 1
    s0 = jnp.zeros((B, di, h.d_state), jnp.float32)
    s_last, ys = jax.lax.scan(chunk_step, s0, (xch, dtch, Bch, Cch))
    y = ys.transpose(1, 0, 2, 3).reshape(B, n_chunks * chunk, di)
    if pad:
        y = y[:, :S]
    y = y + p["D"] * xin[:, :S].astype(jnp.float32)
    y = y.astype(cdt) * jax.nn.silu(z)
    if rules is not None:
        y = rules.constrain(y, "batch", None, "ffn")
    out = y @ p["out_proj"].astype(cdt)
    if return_state:
        return out, {"conv": conv_state, "ssm": s_last}
    return out, None


def mamba_init_state(cfg, batch, dtype=jnp.float32):
    h = cfg.hybrid
    di = h.expand * cfg.d_model
    return {"conv": jnp.zeros((batch, h.d_conv - 1, di), dtype),
            "ssm": jnp.zeros((batch, di, h.d_state), jnp.float32)}
