"""jit'd public wrappers for the Pallas kernels.

Three serving entry points, all drop-ins for ``core/models.py`` applies:

* :func:`conv_tower_apply` — mirrors ``conv_apply`` but runs the fused
  Conv1D+ReLU+MaxPool tower kernel (embedding gather in plain jnp;
  kept for composability and as the bench's half-fused rung).
* :func:`conv_forward_apply` — the full fusion: token ids in,
  per-target predictions out. The embedding gather and pad mask run in
  XLA; the conv tower, FC stack and stacked linear heads run in one
  ``pallas_call`` with no intermediate HBM traffic.
* :func:`lstm_forward_apply` — mirrors ``lstm_apply``: the input
  projection stays a plain XLA matmul, the recurrence runs in the
  Pallas ``lstm_scan`` kernel with the carry in VMEM.

Params may be f32 or bf16 (accumulation is f32 in-kernel either way).
Interpret mode is used only when the backend is the CPU (the test
tier). On any other backend the kernels are compiled, and a kernel the
compiler refuses raises: there is no fallback to interpret mode or to
the plain-jnp apply. ``tests/test_tpu_compile.py`` compiles them for a
described v5e chip.
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp

from repro.core.models import fc_finish, model_heads, scalar_head
from repro.kernels.conv1d_stack import conv1d_stack_fused, conv_forward_fused
from repro.kernels.lstm_scan import lstm_scan_fused
from repro.kernels import ref as REF

# Model kinds with a fused Pallas serving forward (see forward_apply).
KERNEL_KINDS = ("conv1d", "lstm")


def _on_cpu() -> bool:
    """Interpret mode iff the backend is the CPU (never a fallback)."""
    return jax.default_backend() == "cpu"


@functools.partial(jax.jit, static_argnames=("bblk", "interpret"))
def conv1d_stack(x, weights: Sequence, biases: Sequence, mask, *,
                 bblk: int = 8, interpret: bool | None = None):
    interp = _on_cpu() if interpret is None else interpret
    return conv1d_stack_fused(x, list(weights), list(biases), mask,
                              bblk=bblk, interpret=interp)


@functools.partial(jax.jit, static_argnames=("bblk", "interpret"))
def lstm_scan(xw, mask, wh, *, bblk: int = 8,
              interpret: bool | None = None):
    interp = _on_cpu() if interpret is None else interpret
    return lstm_scan_fused(xw, mask, wh, bblk=bblk, interpret=interp)


def conv_tower_apply(params, ids, *, use_kernel: bool = True,
                     interpret: bool | None = None):
    """Drop-in for core.models.conv_apply using the fused tower kernel
    (gather outside the kernel; see conv_forward_apply for full fusion)."""
    mask = (ids != 0).astype(jnp.float32)
    x = params["emb"][ids] * mask[..., None].astype(params["emb"].dtype)
    weights = [lyr["w"] for lyr in params["convs"]]
    biases = [lyr["b"] for lyr in params["convs"]]
    if use_kernel:
        h = conv1d_stack(x, weights, biases, mask, interpret=interpret)
    else:
        h = REF.conv1d_stack_ref(x, weights, biases, mask)
    return fc_finish(params, h)


def _stacked_heads(params):
    """(head_w, head_b, names) with per-target columns stacked so every
    head is one matmul. Single-head layout: the head is ``fc[-1]``."""
    names = model_heads(params)
    if names is None:
        head = params["fc"][-1]
        return head["w"], head["b"], None
    hs = [params["heads"][t] for t in names]
    return (jnp.concatenate([h["w"] for h in hs], axis=1),
            jnp.concatenate([h["b"] for h in hs], axis=0), names)


def conv_forward_apply(params, ids, *, interpret: bool | None = None,
                       bblk: int = 8):
    """Full fused serving forward for kind="conv1d": ids -> predictions.

    Output matches ``conv_apply``: a ``{target: (B,)}`` dict for the
    multi-head layout, a ``(B,)`` array for single-head — but always
    float32 (the kernel accumulates f32 even for bf16 params)."""
    head_w, head_b, names = _stacked_heads(params)
    hidden_fc = params["fc"] if names is not None else params["fc"][:-1]
    out = _conv_forward(
        ids, params["emb"],
        tuple(lyr["w"] for lyr in params["convs"]),
        tuple(lyr["b"] for lyr in params["convs"]),
        tuple(lyr["w"] for lyr in hidden_fc),
        tuple(lyr["b"] for lyr in hidden_fc),
        head_w, head_b, bblk=bblk, interpret=interpret)
    if names is None:
        return out[:, 0]
    return {t: out[:, i] for i, t in enumerate(names)}


@functools.partial(jax.jit, static_argnames=("bblk", "interpret"))
def _conv_forward(ids, emb, conv_ws, conv_bs, fc_ws, fc_bs, head_w,
                  head_b, *, bblk: int = 8,
                  interpret: bool | None = None):
    interp = _on_cpu() if interpret is None else interpret
    return conv_forward_fused(ids, emb, list(conv_ws), list(conv_bs),
                              list(fc_ws), list(fc_bs), head_w, head_b,
                              bblk=bblk, interpret=interp)


def lstm_forward_apply(params, ids, *, interpret: bool | None = None,
                       bblk: int = 8):
    """Fused serving forward for kind="lstm": input projection in XLA,
    recurrence in the Pallas lstm_scan kernel, heads on the f32 hidden
    state. Output matches ``lstm_apply`` (f32)."""
    mask = (ids != 0).astype(jnp.float32)
    x = params["emb"][ids]
    xw = x @ params["wx"] + params["b"]
    h = lstm_scan(xw, mask, params["wh"], bblk=bblk, interpret=interpret)
    names = model_heads(params)
    if names is None:
        return scalar_head(params["head"], h)
    return {t: scalar_head(params["heads"][t], h) for t in names}


def forward_apply(kind: str, params, ids, *,
                  interpret: bool | None = None):
    """Dispatch to the fused Pallas forward for ``kind``.

    Raises ValueError for kinds without a kernel (see KERNEL_KINDS)."""
    if kind == "conv1d":
        return conv_forward_apply(params, ids, interpret=interpret)
    if kind == "lstm":
        return lstm_forward_apply(params, ids, interpret=interpret)
    raise ValueError(
        f"use_kernel supports kinds {KERNEL_KINDS}, not {kind!r}")
