"""Pallas TPU kernel for the LSTM cost-model recurrence.

``core/models.py::lstm_encode`` is the paper's middle model: a masked
LSTM scan whose final hidden state feeds the regression heads. The
input projection ``xw = x @ wx + b`` is one large batched matmul that
XLA already runs at MXU peak, so it stays outside; what XLA lowers
poorly is the *recurrence* — ``lax.scan`` emits a dynamic-slice +
matmul + elementwise chain per step, spilling the ``(B, H)`` carry to
HBM between steps. This kernel runs the whole sequence loop inside one
grid step:

* the wrapper makes the inputs time-major (``(S, B, 4H)`` gates and an
  ``(S, B, 1)`` mask), so step ``t`` reads ``xw_ref[t]`` and
  ``mask_ref[t]`` on the ref's leading dim — the only dynamic index
  Mosaic lowers inside a ``fori_loop``;
* gates ``h @ wh`` as an MXU matmul per step (``wh`` pinned in VMEM);
* the ``(h, c)`` carry lives in VMEM registers across the
  ``fori_loop`` — zero HBM traffic between timesteps;
* masked-carry semantics identical to ``core/models.py::step``: padded
  positions pass the previous ``(h, c)`` through unchanged, and the
  forget gate keeps the paper's +1.0 bias.

Params/activations may be f32 or bf16; the carry and all gate math are
float32 in-kernel either way (bf16 HBM reads, f32 accumulation), and
the final hidden state comes out float32.

VMEM per grid step: the xw tile S*bblk*4H*itemsize, double-buffered.
At ``COSTMODEL_BASE`` (S<=256, bblk=8, H=128) that is 4 MiB per buffer
in f32, inside v5e's default 16 MiB scoped VMEM; S=1024 at H=128 f32
would need 32 MiB and must pass bf16 ``xw`` or a smaller ``bblk``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _lstm_kernel(xw_ref, mask_ref, wh_ref, out_ref, *, hidden: int):
    wh = wh_ref[...].astype(jnp.float32)      # (H, 4H)
    S, bblk, _ = xw_ref.shape

    def step(t, carry):
        h, c = carry
        gates = xw_ref[t].astype(jnp.float32) + h @ wh    # (bblk, 4H)
        i, f, g, o = jnp.split(gates, 4, axis=-1)
        i = jax.nn.sigmoid(i)
        f = jax.nn.sigmoid(f + 1.0)           # paper's forget-gate bias
        o = jax.nn.sigmoid(o)
        g = jnp.tanh(g)
        c_new = f * c + i * g
        h_new = o * jnp.tanh(c_new)
        keep = mask_ref[t]                    # (bblk, 1) f32
        return (h_new * keep + h * (1 - keep),
                c_new * keep + c * (1 - keep))

    h0 = jnp.zeros((bblk, hidden), jnp.float32)
    h, _ = jax.lax.fori_loop(0, S, step, (h0, h0))
    out_ref[...] = h


def lstm_scan_fused(xw: jax.Array, mask: jax.Array, wh: jax.Array, *,
                    bblk: int = 8, interpret: bool = False) -> jax.Array:
    """Masked LSTM recurrence: precomputed gates in, final hidden out.

    xw: (B, S, 4H) = x @ wx + b (f32 or bf16); mask: (B, S) (1 = valid);
    wh: (H, 4H). Returns (B, H) float32. Pads B to a bblk multiple
    (pad rows are fully masked, so their carry stays zero)."""
    B, S, four_h = xw.shape
    hidden = wh.shape[0]
    assert four_h == 4 * hidden, (four_h, hidden)
    mask = mask.astype(jnp.float32)
    Bp = ((B + bblk - 1) // bblk) * bblk
    if Bp != B:
        xw = jnp.pad(xw, ((0, Bp - B), (0, 0), (0, 0)))
        mask = jnp.pad(mask, ((0, Bp - B), (0, 0)))
    xw_t = xw.transpose(1, 0, 2)              # (S, Bp, 4H)
    mask_t = mask.T[..., None]                # (S, Bp, 1)

    out = pl.pallas_call(
        functools.partial(_lstm_kernel, hidden=hidden),
        grid=(Bp // bblk,),
        in_specs=[
            pl.BlockSpec((S, bblk, four_h), lambda i: (0, i, 0)),
            pl.BlockSpec((S, bblk, 1), lambda i: (0, i, 0)),
            pl.BlockSpec(wh.shape, lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bblk, hidden), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Bp, hidden), jnp.float32),
        interpret=interpret,
    )(xw_t, mask_t, wh)
    return out[:B]
