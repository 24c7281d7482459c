"""AI21-Jamba2-Mini through ``repro.models`` against the plain reference
(``benchmarks/chip/jamba_reference.py``) at a small size on the CPU:
each kind of layer and one whole period, forward and decode through
the cache; the expert-parallel share of an MoE layer; and the front
door on the real layer programs (trip counts, no lossy mapping, calls
inlined)."""
import os
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_arch
from repro.core import tokenizer as TOK
from repro.ir import frontdoor as FD
from repro.ir import stablehlo as SH
from repro.models import layers as L
from repro.models import mamba as MB
from repro.models import model as MODEL
from repro.models import moe as M

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks", "chip"))
import jamba_reference as JR  # noqa: E402

B, S = 2, 12
TOL = 2e-5          # float32 at "highest" on both sides


def ref_widths(cfg):
    """The reference's sizes from the system's config (same meaning)."""
    hb, m = cfg.hybrid, cfg.moe
    return {"d_model": cfg.d_model, "n_heads": cfg.n_heads,
            "n_kv_heads": cfg.n_kv_heads, "head_dim": cfg.resolved_head_dim,
            "d_ff": cfg.d_ff, "n_experts": m.n_experts, "top_k": m.top_k,
            "d_state": hb.d_state, "d_conv": hb.d_conv, "expand": hb.expand,
            "dt_rank": hb.resolved_dt_rank(cfg.d_model), "eps": cfg.norm_eps,
            "period": hb.period, "attn_offset": hb.attn_index,
            "expert_period": m.moe_every, "expert_offset": m.moe_offset}


@pytest.fixture(scope="module")
def small():
    """Reduced jamba2-mini (4-layer period, 4 experts) with every vector
    parameter (norms, biases, D) moved off its init value, so that each
    term of the equations shows in the output."""
    cfg = get_arch("jamba2-mini").reduced()
    params = MODEL.init_params(jax.random.PRNGKey(0), cfg)
    pp = jax.tree.map(lambda a: a[0], params["periods"])
    moved = ("ln1", "ln2", "conv_b", "D", "dt_norm", "b_norm", "c_norm")
    paths, tree = jax.tree_util.tree_flatten_with_path(pp)
    ks = jax.random.split(jax.random.PRNGKey(1), len(paths))
    leaves = [a + 0.3 * jax.random.normal(k, a.shape)
              if jax.tree_util.keystr(path[-1:]).strip("[']") in moved
              else a for k, (path, a) in zip(ks, paths)]
    pp = jax.tree.unflatten(tree, leaves)
    x = jax.random.normal(jax.random.PRNGKey(2), (B, S, cfg.d_model))
    return cfg, pp, x, ref_widths(cfg)


def _gap(a, b):
    return JR.rel_gap(a, b)


def _take(tree, i):
    return jax.tree.map(lambda a: a[i], tree)


def _layer(cfg, pp, kind):
    return {"attention": lambda: pp["attn"],
            "mamba": lambda: _take(pp["mamba"], 0),
            "moe": lambda: _take(pp["moe"], 0),
            "ffn": lambda: _take(pp["ffn"], 0)}[kind]()


def _system(cfg, kind, p, x, **kw):
    f32 = jnp.float32
    if kind == "attention":
        pos = jnp.broadcast_to(jnp.arange(x.shape[1]), x.shape[:2])
        return L.attention_apply(p, x, cfg, positions=pos, cdt=f32)[0]
    if kind == "mamba":
        return MB.mamba_apply(p, x, cfg, cdt=f32)[0]
    if kind == "moe":
        return M.moe_apply(p, x, cfg, cdt=f32, **kw)[0]
    return L.ffn_apply(p, x, cdt=f32)


def _system_decode(cfg, kind, p, x):
    """The last position through the decode path: a prefill of the
    others writes the cache (attention) or hands over the state (Mamba),
    then one step."""
    f32 = jnp.float32
    n = x.shape[1] - 1
    last = x[:, n:]
    if kind == "attention":
        pos = jnp.broadcast_to(jnp.arange(n), (x.shape[0], n))
        kv = L.attention_kv(p, x[:, :n], cfg, positions=pos, cdt=f32)
        cache = {k: jnp.pad(v, ((0, 0), (0, 0), (0, 4), (0, 0)))
                 for k, v in kv.items()}
        return L.attention_apply(
            p, last, cfg, positions=jnp.full((x.shape[0], 1), n), cdt=f32,
            cache=cache, cache_index=jnp.int32(n))[0]
    if kind == "mamba":
        _, st = MB.mamba_apply(p, x[:, :n], cfg, cdt=f32, return_state=True)
        return MB.mamba_apply(p, last, cfg, cdt=f32, state=st)[0]
    return _system(cfg, kind, p, last)


@pytest.mark.parametrize("kind", ["attention", "mamba", "moe", "ffn"])
def test_layer_matches_reference(small, kind):
    """Each kind of layer, forward and one decode step, against the
    reference's full forward (decode: its last position)."""
    cfg, pp, x, c = small
    p = _layer(cfg, pp, kind)
    with jax.default_matmul_precision("highest"):
        want = JR.LAYERS[kind](p, x, c)
        got = _system(cfg, kind, p, x)
        step = _system_decode(cfg, kind, p, x)
        want_last = JR.decode_last(JR.LAYERS[kind], p, x, c)
    assert _gap(got, want) < TOL
    assert _gap(step, want_last) < TOL


def test_period_matches_reference(small):
    """One whole period (attention at slot 2, MoE on the odd slots):
    the system's scanned block against the reference's layers."""
    cfg, pp, x, c = small
    assert [k for k in JR.layer_kinds(c)] == [
        ("mamba", "ffn"), ("mamba", "moe"), ("attention", "ffn"),
        ("mamba", "moe")]
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    with jax.default_matmul_precision("highest"):
        got, _, _ = MODEL._period_apply(pp, x, cfg, positions=pos,
                                        rules=None, cdt=jnp.float32)
        want = JR.period(pp, x, c)
    assert _gap(got, want) < TOL


def test_period_decode_through_cache(small):
    """Token by token through the period's KV cache and Mamba states:
    every step equals the reference's full forward at that position."""
    cfg, pp, x, c = small
    caches = jax.tree.map(lambda a: a[0],
                          MODEL.init_cache(cfg, B, S, kv_dtype=jnp.float32))
    outs = []
    with jax.default_matmul_precision("highest"):
        want = JR.period(pp, x, c)
        for i in range(S):
            y, caches, _ = MODEL._period_apply(
                pp, x[:, i:i + 1], cfg, positions=jnp.full((B, 1), i),
                rules=None, cdt=jnp.float32, caches=caches,
                cache_index=jnp.int32(i))
            outs.append(y)
    assert _gap(jnp.concatenate(outs, axis=1), want) < TOL


def test_expert_shares_add_up_to_the_layer(small):
    """Expert parallelism (the model-configs guide, section 4): the parts
    that two chips holding experts 0-1 and 2-3 compute add up to the
    uncut layer, and each equals the reference given the same share."""
    cfg, pp, x, c = small
    p = _take(pp["moe"], 0)
    n = cfg.moe.n_experts
    half = {k: (v if k == "router" else v[:n // 2]) for k, v in p.items()}
    rest = {k: (v if k == "router" else v[n // 2:]) for k, v in p.items()}
    with jax.default_matmul_precision("highest"):
        whole = _system(cfg, "moe", p, x)
        a = _system(cfg, "moe", half, x)
        b = _system(cfg, "moe", rest, x, first_expert=n // 2)
        ref_a = JR.moe(half, x, c)
        ref_b = JR.moe(rest, x, c, first_expert=n // 2)
        ref_whole = JR.moe(p, x, c)
    assert _gap(a + b, whole) < TOL
    assert _gap(a, ref_a) < TOL and _gap(b, ref_b) < TOL
    assert _gap(whole, ref_whole) < TOL


def test_moe_is_dropless_and_unnormalized(small):
    """A zero router sends every token to experts 0 and 1 (ties go to
    the lower index) with weight 1/4 each: a capacity would drop most of
    them, renormalizing would double the output."""
    cfg, pp, x, c = small
    p = dict(_take(pp["moe"], 0), router=jnp.zeros_like(
        pp["moe"]["router"][0]))
    with jax.default_matmul_precision("highest"):
        got = _system(cfg, "moe", p, x)
        want = JR.moe(p, x, c)
        two = {k: (v if k == "router" else v[:2]) for k, v in p.items()}
        only = 0.25 * (JR.ffn(_take(two, 0), x) + JR.ffn(_take(two, 1), x))
    assert _gap(got, want) < TOL
    assert _gap(want, only) < TOL


# ------------------------------------------------------- front door
@pytest.fixture(scope="module")
def programs():
    return {kind: SH.layer_programs("jamba2-mini", kind, 1, 2048)
            for kind in SH.PROGRAM_KINDS}


# chunks of the published-width programs at 2,048 positions: Mamba's
# scan 2048 / 256, the MoE dispatch 2048 / 1024, flash attention's key
# blocks 2048 / 1024; a decode step's MoE maps its one chunk
TRIPS = {("train", "attention"): [2, 2], ("train", "mamba"): [8, 8],
         ("train", "moe"): [2, 2], ("train", "ffn"): [],
         ("prefill", "attention"): [2], ("prefill", "mamba"): [8],
         ("prefill", "moe"): [2], ("prefill", "ffn"): [],
         ("decode", "attention"): [], ("decode", "mamba"): [],
         ("decode", "moe"): [1], ("decode", "ffn"): []}


@pytest.mark.parametrize("kind", ["train", "prefill", "decode"])
def test_frontdoor_keeps_real_layer_programs(programs, kind):
    """Published-width Jamba layer programs: every op keeps its own
    opcode (the reference's count of the text), every loop its trip
    count, every ``func.call`` its callee's ops."""
    for layer, text in programs[kind]:
        g, st = FD.parse_mlir_stats(text)
        assert g is not None, layer
        assert st.lossy == 0 and st.unknown_trips == 0, (layer, st)
        assert JR.lossy_ops(text, g) == 0, layer
        trips = [op.attrs["trips"] for op in g.ops
                 if op.opcode == FD.WHILE_OP]
        assert sorted(trips) == sorted(TRIPS[kind, layer]), (layer, trips)
        assert JR.text_ops(text)[1] == trips
        assert "call" not in {op.opcode for op in g.ops}
        toks = TOK.graph_tokens(g, "ops_operands")
        for t in trips:
            assert TOK.trips_token(t) in toks


def test_frontdoor_inlines_calls_and_maps_ops():
    """A call's callee is inlined at each call site; compare, convert,
    select, power, sqrt, iota, top_k and log_plus_one keep their names;
    a loop whose bound is an argument carries the unknown count."""
    def fn(x, n):
        def body(i, acc):
            return acc * 2.0 + jnp.sqrt(jnp.abs(acc)) ** 1.5
        y = jax.lax.fori_loop(0, n, body, x)
        top = jax.lax.top_k(y, 2)[0]
        z = jnp.where(y > 0, jax.nn.softplus(y), y.astype(jnp.int32))
        return z, top, jnp.arange(4.0)
    text = jax.jit(fn).lower(jax.ShapeDtypeStruct((8,), jnp.float32),
                             jax.ShapeDtypeStruct((), jnp.int32)).as_text()
    g, st = FD.parse_mlir_stats(text)
    ops = {op.opcode for op in g.ops}
    for name in ("compare", "convert", "select", "power", "sqrt", "iota",
                 "top_k", "log_plus_one"):
        assert name in ops, name
    assert "call" not in ops
    assert st.whiles == 1 and st.unknown_trips == 1
    assert [op.attrs["trips"] for op in g.ops
            if op.opcode == FD.WHILE_OP] == [None]
    assert TOK.trips_token(None) in TOK.graph_tokens(g, "ops")
    assert JR.lossy_ops(text, g) == 0


def test_vocab_reserves_loop_tokens():
    v = TOK.fit_vocab([["xpu.add", "8x8xf32"]], max_size=4096)
    for t in TOK.LOOP_TOKENS:
        assert t in v.token_to_id
    assert v.token_to_id["xpu.add"] < v.token_to_id[TOK.trips_token(None)]
    # a vocabulary that fills up keeps its counted tokens first
    small = TOK.fit_vocab([["xpu.add", "8x8xf32"]], max_size=16)
    assert {"xpu.add", "8x8xf32"} <= set(small.token_to_id)
    assert len(small.token_to_id) == 16
