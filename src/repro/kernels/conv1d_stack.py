"""Pallas TPU kernels: the fused Conv1D serving forward for the cost model.

The paper's deployed model runs thousands of inferences per compilation
session, so this is the perf-critical hot spot. A naive XLA lowering runs
each Conv1D as a separate HBM round-trip (6 layers x (B,S,C) activations);
at C=64 the tower is heavily memory-bound (arithmetic intensity ~= fs*C/6
FLOPs/byte). Two fusion levels live here, both taking the same
``(B, S, E)`` embedded activations:

* :func:`conv1d_stack_fused` — the tower only: embedded activations in,
  masked-pooled features out.
* :func:`conv_forward_fused` — the full serving forward: **token ids
  in, per-target predictions out**. The embedding gather and pad mask
  run in XLA in the wrapper (one HBM write + read of the ``(B, S, E)``
  activations); the conv tower, max-pool, FC stack and stacked
  per-target linear heads run in one ``pallas_call``, so no conv layer
  or pooled/FC feature ever leaves VMEM. Keeping the gather out of the
  kernel keeps the embedding table out of VMEM: Mosaic cannot lower an
  in-kernel ``jnp.take`` gather, and a pinned table would not fit VMEM
  at ``COSTMODEL_100M`` widths (64 MiB in f32).

TPU mapping:
* channels sit on the lane dimension;
* sequence sits on sublanes; each conv tap is a (S, Cin) @ (Cin, Cout)
  MXU matmul — the fs-tap conv = fs shifted matmuls accumulated in fp32;
* grid over batch tiles; weights are broadcast to every grid step
  (index_map pins them to block 0), so they are held whole in VMEM.

Accumulation is float32 regardless of the parameter dtype, so bf16-cast
params (quantized serving) run bf16 HBM reads with f32 in-kernel math;
predictions always come out float32.

VMEM per grid step (bblk=8): the x tile bblk*S*E*4 B (0.5 MiB at
``COSTMODEL_BASE``: S=256, E=64), two f32 layer buffers of bblk*S*C*4 B
each, and every pinned weight. At ``COSTMODEL_BASE`` the weights are
well under 1 MiB; at ``COSTMODEL_100M`` the pinned conv + FC weights
alone are 184 MiB in f32 and do not fit, so that width needs a non-pinned
(streamed) weight layout before it can serve through this kernel.
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _pinned_spec(shape):
    """BlockSpec broadcasting one whole operand to every grid step.

    The index map must not close over loop variables (a late-binding
    ``lambda i: (0,) * w.ndim`` inside the operand loop would see only
    the final ``w``), so the rank is bound here, per call."""
    n = len(shape)
    return pl.BlockSpec(shape, lambda i, _n=n: (0,) * _n)


def _tower(h, mask, refs, n_layers, filter_sizes, *, masked_pool):
    """Conv tower + ReLU per layer + MaxPool, all in f32 VMEM.

    ``refs[2i], refs[2i+1]`` are the layer-i weight/bias refs. Returns
    (bblk, C_last) pooled features. ``masked_pool`` excludes pad
    positions from the max (the tower-only kernel's contract, matching
    conv1d_stack_ref(mask); all-pad rows pool to the ReLU floor of 0);
    the full-forward kernel pools every position, exactly matching
    core/models.py::conv_apply, and passes ``mask=None``."""
    S = h.shape[1]
    for i in range(n_layers):
        w = refs[2 * i][...].astype(jnp.float32)      # (fs, Cin, Cout)
        b = refs[2 * i + 1][...].astype(jnp.float32)  # (Cout,)
        fs = filter_sizes[i]
        pad_l, pad_r = (fs - 1) // 2, fs // 2
        acc = jnp.zeros(h.shape[:2] + (w.shape[2],), jnp.float32)
        # conv = sum of shifted matmuls on the MXU
        hp = jnp.pad(h, ((0, 0), (pad_l, pad_r), (0, 0)))
        for k in range(fs):
            acc += jax.lax.dot_general(
                hp[:, k:k + S, :], w[k],
                dimension_numbers=(((2,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        h = jnp.maximum(acc + b, 0.0)             # ReLU
    if not masked_pool:
        return h.max(axis=1)                      # MaxPool1D, all positions
    h = jnp.where(mask[..., None] > 0, h, -jnp.inf)
    return jnp.maximum(h.max(axis=1), 0.0)


def _kernel(x_ref, mask_ref, *refs, n_layers: int, filter_sizes, out_dtype):
    """Tower-only kernel. refs = (w0, b0, w1, b1, ..., out_ref)."""
    out_ref = refs[-1]
    x = x_ref[...].astype(jnp.float32)            # (bblk, S, C0)
    mask = mask_ref[...]                          # (bblk, S)
    out_ref[...] = _tower(x, mask, refs[:-1], n_layers, filter_sizes,
                          masked_pool=True).astype(out_dtype)


def conv1d_stack_fused(x: jax.Array, weights: Sequence[jax.Array],
                       biases: Sequence[jax.Array],
                       mask: jax.Array, *, bblk: int = 8,
                       interpret: bool = False) -> jax.Array:
    """Fused tower. x: (B, S, C0); mask: (B, S) (1 = valid token).
    Returns (B, C_last). Pads B to a bblk multiple and C dims are used
    as given (pad to 128 upstream for lane alignment on real hardware)."""
    B, S, C0 = x.shape
    n_layers = len(weights)
    filter_sizes = tuple(int(w.shape[0]) for w in weights)
    c_last = weights[-1].shape[2]
    Bp = ((B + bblk - 1) // bblk) * bblk
    if Bp != B:
        x = jnp.pad(x, ((0, Bp - B), (0, 0), (0, 0)))
        mask = jnp.pad(mask, ((0, Bp - B), (0, 0)))
    grid = (Bp // bblk,)

    in_specs = [
        pl.BlockSpec((bblk, S, C0), lambda i: (i, 0, 0)),
        pl.BlockSpec((bblk, S), lambda i: (i, 0)),
    ]
    operands = [x, mask]
    for w, b in zip(weights, biases):
        in_specs.append(_pinned_spec(w.shape))
        in_specs.append(_pinned_spec(b.shape))
        operands += [w, b]

    out = pl.pallas_call(
        functools.partial(_kernel, n_layers=n_layers,
                          filter_sizes=filter_sizes, out_dtype=x.dtype),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bblk, c_last), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Bp, c_last), x.dtype),
        interpret=interpret,
    )(*operands)
    return out[:B]


def _forward_kernel(x_ref, *refs, n_layers: int, filter_sizes, n_fc: int):
    """Embedded-activations-in / predictions-out kernel.

    refs = (w0, b0, ..., w{L-1}, b{L-1},          conv tower
            fw0, fb0, ..., fw{n_fc-1}, fb{n_fc-1}, hidden FC stack
            head_w, head_b, out_ref)              stacked linear heads
    """
    out_ref = refs[-1]
    x = x_ref[...].astype(jnp.float32)            # (bblk, S, E), pads = 0
    # pool over every position (pads included), exactly like conv_apply:
    # the serving tier's bucket pad_slack relies on those semantics
    pooled = _tower(x, None, refs[:2 * n_layers], n_layers, filter_sizes,
                    masked_pool=False)
    # hidden FC stack (ReLU), then all heads as ONE (F, n_heads) matmul
    off = 2 * n_layers
    h = pooled
    for i in range(n_fc):
        fw = refs[off + 2 * i][...].astype(jnp.float32)
        fb = refs[off + 2 * i + 1][...].astype(jnp.float32)
        h = jnp.maximum(h @ fw + fb, 0.0)
    head_w = refs[off + 2 * n_fc][...].astype(jnp.float32)   # (F, n_heads)
    head_b = refs[off + 2 * n_fc + 1][...].astype(jnp.float32)
    out_ref[...] = h @ head_w + head_b


def conv_forward_fused(ids: jax.Array, emb: jax.Array,
                       conv_weights: Sequence[jax.Array],
                       conv_biases: Sequence[jax.Array],
                       fc_weights: Sequence[jax.Array],
                       fc_biases: Sequence[jax.Array],
                       head_w: jax.Array, head_b: jax.Array, *,
                       bblk: int = 8,
                       interpret: bool = False) -> jax.Array:
    """The full fused serving forward: token ids -> (B, n_heads) f32.

    ids: (B, S) int32 (PAD id 0); emb: (V, E); head_w: (F, n_heads)
    with the per-target head columns stacked. Params may be f32 or bf16
    — accumulation is f32 in-kernel either way. The embedding gather and
    pad mask run in XLA; everything after them is one ``pallas_call``
    whose only HBM write is the predictions."""
    B, S = ids.shape
    n_layers = len(conv_weights)
    filter_sizes = tuple(int(w.shape[0]) for w in conv_weights)
    n_fc = len(fc_weights)
    n_heads = head_w.shape[1]
    Bp = ((B + bblk - 1) // bblk) * bblk
    if Bp != B:
        ids = jnp.pad(ids, ((0, Bp - B), (0, 0)))   # pad rows are all-PAD
    mask = (ids != 0).astype(emb.dtype)             # PAD id is 0
    x = jnp.take(emb, ids, axis=0) * mask[..., None]
    grid = (Bp // bblk,)

    in_specs = [pl.BlockSpec((bblk, S, emb.shape[1]), lambda i: (i, 0, 0))]
    operands = [x]
    for w, b in zip(conv_weights, conv_biases):
        in_specs += [_pinned_spec(w.shape), _pinned_spec(b.shape)]
        operands += [w, b]
    for w, b in zip(fc_weights, fc_biases):
        in_specs += [_pinned_spec(w.shape), _pinned_spec(b.shape)]
        operands += [w, b]
    in_specs += [_pinned_spec(head_w.shape), _pinned_spec(head_b.shape)]
    operands += [head_w, head_b]

    out = pl.pallas_call(
        functools.partial(_forward_kernel, n_layers=n_layers,
                          filter_sizes=filter_sizes, n_fc=n_fc),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bblk, n_heads), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Bp, n_heads), jnp.float32),
        interpret=interpret,
    )(*operands)
    return out[:B]
