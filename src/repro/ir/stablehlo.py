"""Real-MLIR pathway: StableHLO text from ``jax.jit(...).lower().as_text()``.

JAX natively emits MLIR (StableHLO dialect), so the served model sees
*genuine* compiler IR, not simulated text. Ground truth for these
samples comes from XLA itself: ``compiled.cost_analysis()`` FLOPs/bytes
and the roofline latency derived from them — i.e. we predict what the
compiler would report, without compiling.

Graph sources:

* :func:`sample_stablehlo_corpus` — a fixed pool of jnp subgraphs
  (mlp / attention / conv / norm-residual) mirroring the xpu op mix.
* :func:`layer_programs` — the real layer programs of a hybrid
  architecture, whose blocks ``repro.models`` implements in full
  (jamba2-mini, jamba-v0.1-52b): its attention, Mamba, MoE and dense
  FFN layers at the published widths, for training (forward and
  backward), prefill and one decode step against a cache, lowered from
  ``jax.ShapeDtypeStruct`` specs (no tensor data materialized).
* :func:`arch_subgraphs` / :func:`lower_arch_corpus` — per-layer
  subgraphs (attention, SwiGLU MLP, norms, router, lm head) of the other
  architectures registered in ``repro.configs.ARCHS``: hand-written
  stand-ins at reduced widths. These, and the real layer programs, are
  the "ingest a program we did not generate" acceptance inputs for the
  front door.

The affine/scf "lower-level dialects produce much larger sequences"
scenario is NOT produced here — nothing in this module lowers to
affine. That corpus lives in
:data:`repro.ir.frontdoor.AFFINE_EXAMPLE`, which the tolerant ingestion
parser (:mod:`repro.ir.frontdoor`) and its fuzz corpus exercise.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.ir.analyzers import HBM_BW, PEAK_FLOPS


def lower_fn(fn: Callable, *args) -> Tuple[str, Dict[str, float]]:
    """Lower fn to StableHLO text and harvest XLA cost analysis targets.

    A program that lowers but does not compile raises. A backend whose
    cost analysis omits ``flops`` or ``bytes accessed`` (XLA's CPU
    backend leaves them out for programs with no such work) yields 0
    for that target."""
    lowered = jax.jit(fn).lower(*args)
    text = lowered.as_text()
    ca = lowered.compile().cost_analysis()
    flops = float(ca.get("flops", 0.0))
    bytes_ = float(ca.get("bytes accessed", 0.0))
    targets = {
        "flops": flops,
        "bytes": bytes_,
        "latency_us": max(flops / PEAK_FLOPS, bytes_ / HBM_BW) * 1e6,
    }
    return text, targets


# A pool of jnp subgraphs mirroring the xpu-dialect op mix.
def _mlp(b, s, d, f):
    def fn(x, w1, w2):
        return jax.nn.gelu(x @ w1) @ w2
    args = (jnp.ones((b, s, d), jnp.float32),
            jnp.ones((d, f), jnp.float32), jnp.ones((f, d), jnp.float32))
    return fn, args


def _attn(b, s, d, h):
    hd = d // h

    def fn(x, wq, wk, wv):
        q = (x @ wq).reshape(b, s, h, hd)
        k = (x @ wk).reshape(b, s, h, hd)
        v = (x @ wv).reshape(b, s, h, hd)
        a = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(hd)
        w = jax.nn.softmax(a, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", w, v).reshape(b, s, d)
    w = jnp.ones((d, d), jnp.float32)
    return fn, (jnp.ones((b, s, d), jnp.float32), w, w, w)


def _conv(b, s, cin, cout):
    def fn(x, w):
        return jax.nn.relu(jax.lax.conv_general_dilated(
            x, w, (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC")))
    return fn, (jnp.ones((b, s, s, cin), jnp.float32),
                jnp.ones((3, 3, cin, cout), jnp.float32))


def _norm_residual(b, s, d):
    def fn(x, g):
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return x + (x - mu) * jax.lax.rsqrt(var + 1e-5) * g
    return fn, (jnp.ones((b, s, d), jnp.float32), jnp.ones((d,), jnp.float32))


def sample_stablehlo_corpus(rng: np.random.Generator, n: int = 64
                            ) -> List[Tuple[str, Dict[str, float]]]:
    """Generate (stablehlo_text, targets) rows by lowering real jnp graphs."""
    rows = []
    makers = [
        lambda: _mlp(int(rng.choice([1, 4, 8])), int(rng.choice([64, 128])),
                     int(rng.choice([128, 256, 512])),
                     int(rng.choice([256, 512, 1024]))),
        lambda: _attn(int(rng.choice([1, 4])), int(rng.choice([64, 128])),
                      int(rng.choice([128, 256])), int(rng.choice([4, 8]))),
        lambda: _conv(int(rng.choice([1, 4])), int(rng.choice([14, 28])),
                      int(rng.choice([16, 32])), int(rng.choice([32, 64]))),
        lambda: _norm_residual(int(rng.choice([1, 8])),
                               int(rng.choice([64, 256])),
                               int(rng.choice([256, 1024]))),
    ]
    for i in range(n):
        fn, args = makers[i % len(makers)]()
        rows.append(lower_fn(fn, *args))
    return rows


# ------------------------------------------- real-architecture subgraphs
def arch_subgraphs(name: str, batch: int = 1, seq: int = 8
                   ) -> List[Tuple[str, Callable, Tuple]]:
    """Per-layer jnp subgraphs of a registered architecture at reduced
    widths: ``(layer_name, fn, arg_specs)`` triples, args as
    ``jax.ShapeDtypeStruct`` so lowering materializes nothing.

    These are the front door's acceptance inputs — real architectures
    from ``configs/``, lowered with ``jax.jit(fn).lower(*specs)``, fed
    back through ``predict_text``."""
    from repro.configs import get_arch
    cfg = get_arch(name).reduced()
    d = cfg.d_model
    h, hd = cfg.n_heads, cfg.resolved_head_dim
    ff = cfg.d_ff or 4 * d
    f32 = jnp.float32

    def spec(*shape):
        return jax.ShapeDtypeStruct(shape, f32)

    def attention(x, wq, wk, wv, wo):
        b, s, _ = x.shape
        q = (x @ wq).reshape(b, s, h, hd)
        k = (x @ wk).reshape(b, s, h, hd)
        v = (x @ wv).reshape(b, s, h, hd)
        a = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(hd)
        w = jax.nn.softmax(a, axis=-1)
        return (jnp.einsum("bhqk,bkhd->bqhd", w, v)
                .reshape(b, s, h * hd)) @ wo

    def mlp_swiglu(x, wg, wu, wd):
        return (jax.nn.silu(x @ wg) * (x @ wu)) @ wd

    def rmsnorm_residual(x, g):
        var = (x * x).mean(-1, keepdims=True)
        return x + x * jax.lax.rsqrt(var + cfg.norm_eps) * g

    def lm_head(x, w):
        return jax.nn.log_softmax(x @ w, axis=-1)

    out: List[Tuple[str, Callable, Tuple]] = [
        ("attention", attention,
         (spec(batch, seq, d), spec(d, h * hd), spec(d, h * hd),
          spec(d, h * hd), spec(h * hd, d))),
        ("mlp_swiglu", mlp_swiglu,
         (spec(batch, seq, d), spec(d, ff), spec(d, ff), spec(ff, d))),
        ("rmsnorm_residual", rmsnorm_residual,
         (spec(batch, seq, d), spec(d))),
        ("lm_head", lm_head, (spec(batch, seq, d), spec(d, cfg.vocab))),
    ]
    if cfg.moe is not None:
        def moe_router(x, wr):
            logits = x @ wr
            probs = jax.nn.softmax(logits, axis=-1)
            top = jax.lax.top_k(probs, cfg.moe.top_k)[0]
            return top / top.sum(-1, keepdims=True)
        out.append(("moe_router", moe_router,
                    (spec(batch, seq, d), spec(d, cfg.moe.n_experts))))
    return out


# --------------------------------------------- real layer programs
LAYER_KINDS = ("attention", "mamba", "moe", "ffn")
PROGRAM_KINDS = ("train", "prefill", "decode")


def _layer_specs(cfg, layer: str):
    """Abstract parameters of one layer at the config's widths (every
    expert of an MoE layer)."""
    from repro.models import layers as L
    from repro.models import mamba as MB
    from repro.models import moe as M
    key = jax.random.PRNGKey(0)
    init = {"attention": lambda: L.attention_init(key, cfg),
            "mamba": lambda: MB.mamba_init(key, cfg),
            "moe": lambda: M.moe_init(key, cfg),
            "ffn": lambda: L.ffn_init(key, cfg.d_model, cfg.d_ff)}[layer]
    return jax.eval_shape(init)


def _layer_fn(cfg, layer: str, cdt):
    """``f(params, x) -> y`` of one layer on a whole sequence."""
    from repro.models import layers as L
    from repro.models import mamba as MB
    from repro.models import moe as M

    def attention(p, x):
        pos = jnp.broadcast_to(jnp.arange(x.shape[1]), x.shape[:2])
        return L.attention_apply(p, x, cfg, positions=pos, cdt=cdt)[0]
    return {"attention": attention,
            "mamba": lambda p, x: MB.mamba_apply(p, x, cfg, cdt=cdt)[0],
            "moe": lambda p, x: M.moe_apply(p, x, cfg, cdt=cdt)[0],
            "ffn": lambda p, x: L.ffn_apply(p, x, cdt=cdt)}[layer]


def layer_program(arch: str, layer: str, kind: str, batch: int,
                  length: int, cdt=jnp.bfloat16) -> Tuple[Callable, Tuple]:
    """``(fn, arg_specs)`` of one layer program of ``arch`` at its
    published widths: ``train`` is the layer's forward and backward
    (``jax.vjp`` against an output cotangent) over ``batch`` x
    ``length`` positions, ``prefill`` its forward, and ``decode`` one
    token per row against a KV cache of ``length`` positions
    (attention) or the conv and SSM state (Mamba); the MoE and FFN
    decode steps do not depend on ``length``."""
    from repro.configs import get_arch
    from repro.models import layers as L
    from repro.models import mamba as MB
    cfg = get_arch(arch)
    if cfg.family != "hybrid" or layer not in LAYER_KINDS:
        raise ValueError(f"no real {layer!r} layer program for {arch!r}")
    p = _layer_specs(cfg, layer)
    d = cfg.d_model

    def spec(*shape, dtype=cdt):
        return jax.ShapeDtypeStruct(shape, dtype)

    fwd = _layer_fn(cfg, layer, cdt)
    if kind == "prefill":
        return fwd, (p, spec(batch, length, d))
    if kind == "train":
        def train(params, x, dy):
            y, pull = jax.vjp(fwd, params, x)
            return y, pull(dy)
        return train, (p, spec(batch, length, d), spec(batch, length, d))
    if kind != "decode":
        raise ValueError(f"unknown program kind {kind!r}")
    x = spec(batch, 1, d)
    if layer == "attention":
        nkv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
        cache = {"k": spec(batch, nkv, length, hd),
                 "v": spec(batch, nkv, length, hd)}

        def step(params, x, cache, index):
            pos = jnp.broadcast_to(index, (x.shape[0], 1))
            return L.attention_apply(params, x, cfg, positions=pos,
                                     cdt=cdt, cache=cache,
                                     cache_index=index)
        return step, (p, x, cache, spec(dtype=jnp.int32))
    if layer == "mamba":
        hb = cfg.hybrid
        di = hb.expand * d
        state = {"conv": spec(batch, hb.d_conv - 1, di),
                 "ssm": spec(batch, di, hb.d_state, dtype=jnp.float32)}
        return (lambda params, x, st: MB.mamba_apply(
            params, x, cfg, cdt=cdt, state=st)), (p, x, state)
    return fwd, (p, x)


def layer_programs(arch: str, kind: str, batch: int, length: int
                   ) -> List[Tuple[str, str]]:
    """``(layer, stablehlo_text)`` of each layer kind of ``arch``
    (attention, Mamba, MoE, dense FFN) for one program kind, batch and
    length (:func:`layer_program`)."""
    out = []
    for layer in LAYER_KINDS:
        fn, specs = layer_program(arch, layer, kind, batch, length)
        out.append((layer, jax.jit(fn).lower(*specs).as_text()))
    return out


def lower_arch_corpus(names: Optional[List[str]] = None, batch: int = 1,
                      seq: int = 8) -> List[Tuple[str, str, str]]:
    """Lower every per-layer subgraph of the given architectures ->
    ``(arch, layer, stablehlo_text)`` rows: the real layer programs
    (prefill at ``batch`` x ``seq``) of the hybrid archs, the stand-ins
    of the others. ``names=None`` lowers all registered
    archs."""
    from repro.configs import ARCHS
    rows: List[Tuple[str, str, str]] = []
    for name in (names if names is not None else sorted(ARCHS)):
        if ARCHS[name].family == "hybrid":
            rows.extend((name, layer, text) for layer, text in
                        layer_programs(name, "prefill", batch, seq))
            continue
        for layer, fn, specs in arch_subgraphs(name, batch=batch,
                                               seq=seq):
            text = jax.jit(fn).lower(*specs).as_text()
            rows.append((name, layer, text))
    return rows
