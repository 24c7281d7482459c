"""The plain reference of AI21-Jamba2-Mini's layers, the layer programs
the ``jamba2-fleet`` cell sends, and the reference's own reading of
those programs' text.

Nothing here imports the program. One copy serves the tier-1 tests (at
a small size on the CPU) and the chip check (at the published widths).

Layers (``attention``, ``mamba``, ``moe``, ``ffn``, ``period``) are the
published equations in straightforward ``jax.numpy``, computed in the
dtype of their input (float32 for a comparison, under
``jax.default_matmul_precision("highest")``, which the caller sets):
no cache, no chunking, no capacity, no batching tricks. A decode step is
the last position of a full forward pass (``decode_last``). The
equations follow transformers' ``JambaAttention``, ``JambaMambaMixer``
(slow path), ``JambaSparseMoeBlock`` and ``JambaMLP``:

* attention: GQA, 32 query and 8 KV heads of 128, causal softmax,
  no positional encoding, no biases;
* Mamba-1: ``in_proj`` to x and z (no bias), a causal depthwise conv
  of width 4 with bias, SiLU, ``x_proj`` to dt, B and C, an RMSNorm on
  each of dt, B and C, ``dt_proj`` with bias, softplus,
  A = -exp(A_log), the selective scan one position after another, the
  D skip, the gate y * SiLU(z), ``out_proj`` (no bias);
* MoE: a router to 16 logits, softmax, the top-2 experts with their
  softmax weights as they are (not renormalized), SwiGLU experts, every
  token served (dropless);
* dense FFN: SwiGLU;
* a period of 8 layers: pre-norm residual blocks, attention at slot
  ``attn_layer_offset``, Mamba elsewhere, MoE where
  ``i % expert_layer_period == expert_layer_offset``, the dense FFN on
  the other layers.

Departures from transformers' Jamba, each deliberate:

* layers only: no embedding, final norm or LM head (the cell costs and
  checks layers);
* an MoE layer may hold a share of the experts (``first_expert`` and the
  leading axis of its expert weights) and then gives that share's part
  of the output, as one chip of an expert-parallel deployment does;
  transformers always holds all 16;
* every position is valid (no attention mask or padding), and the
  router's auxiliary loss is not computed;
* float32 throughout, where the checkpoint is bfloat16.
"""
from __future__ import annotations

import re
from collections import Counter
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


# ------------------------------------------------------------ widths
def widths(arch: dict) -> dict:
    """The sizes the layers need, from a config.json-style dict
    (``hidden_size``, ``num_attention_heads``, ...)."""
    d = arch["hidden_size"]
    return {
        "d_model": d,
        "n_heads": arch["num_attention_heads"],
        "n_kv_heads": arch["num_key_value_heads"],
        "head_dim": (arch.get("head_dim")
                     or d // arch["num_attention_heads"]),
        "d_ff": arch["intermediate_size"],
        "n_experts": arch["num_experts"],
        "top_k": arch["num_experts_per_tok"],
        "d_state": arch["mamba_d_state"],
        "d_conv": arch["mamba_d_conv"],
        "expand": arch["mamba_expand"],
        "dt_rank": arch["mamba_dt_rank"],
        "eps": arch["rms_norm_eps"],
        "period": arch["attn_layer_period"],
        "attn_offset": arch["attn_layer_offset"],
        "expert_period": arch["expert_layer_period"],
        "expert_offset": arch["expert_layer_offset"],
    }


def layer_kinds(c: dict) -> List[Tuple[str, str]]:
    """(mixer, feed-forward) of each layer of one period."""
    return [("attention" if i % c["period"] == c["attn_offset"] else "mamba",
             "moe" if i % c["expert_period"] == c["expert_offset"]
             else "ffn") for i in range(c["period"])]


# ------------------------------------------------------------ layers
def rms_norm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(x.dtype)


def silu(x):
    return x * jax.nn.sigmoid(x)


def _w(p, name, x):
    return p[name].astype(x.dtype)


def _project(p, x, c):
    q = jnp.einsum("bsd,dhk->bshk", x, _w(p, "wq", x))
    k = jnp.einsum("bsd,dhk->bshk", x, _w(p, "wk", x))
    v = jnp.einsum("bsd,dhk->bshk", x, _w(p, "wv", x))
    return q, k, v


def _attend(q, k, v, q_pos, k_pos, c):
    """softmax(q k^T / sqrt(D) + causal mask) v with the KV heads
    repeated to the query heads. q: (B, Sq, H, D); k, v: (B, Sk, Hkv, D)."""
    g = c["n_heads"] // c["n_kv_heads"]
    k = jnp.repeat(k, g, axis=2)
    v = jnp.repeat(v, g, axis=2)
    scale = float(np.sqrt(q.shape[-1]))
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) / scale
    mask = k_pos[None, :] <= q_pos[:, None]
    logits = jnp.where(mask[None, None], logits, -jnp.inf)
    w = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", w, v)


def attention(p, x, c, rows: Optional[Tuple] = None):
    """Causal GQA attention, no positional encoding. ``rows`` =
    (first, count) gives the outputs of those positions only: one block
    of the full forward (``first`` may be traced)."""
    q, k, v = _project(p, x, c)
    S = x.shape[1]
    lo, n = rows or (0, S)
    if rows is not None:
        q = jax.lax.dynamic_slice_in_dim(q, lo, n, axis=1)
    o = _attend(q, k, v, lo + jnp.arange(n), jnp.arange(S), c)
    return jnp.einsum("bshk,hkd->bsd", o, _w(p, "wo", x))


def _ssm_inputs(p, u, c):
    """dt (B,S,di), B (B,S,N), C (B,S,N) and A (di,N) from the conv's
    output ``u``."""
    r, n = c["dt_rank"], c["d_state"]
    proj = u @ _w(p, "x_proj", u)
    dt, bm, cm = proj[..., :r], proj[..., r:r + n], proj[..., r + n:]
    dt = rms_norm(dt, p["dt_norm"], c["eps"])
    bm = rms_norm(bm, p["b_norm"], c["eps"])
    cm = rms_norm(cm, p["c_norm"], c["eps"])
    delta = jax.nn.softplus(dt @ _w(p, "dt_proj", u) + _w(p, "dt_bias", u))
    a = -jnp.exp(p["A_log"].astype(u.dtype))
    return delta, bm, cm, a


def _conv(p, xs, c):
    k = c["d_conv"]
    w = _w(p, "conv_w", xs)
    S = xs.shape[1] - (k - 1)
    out = sum(xs[:, i:i + S] * w[i] for i in range(k))
    return out + _w(p, "conv_b", xs)


def mamba(p, x, c):
    """The Mamba-1 mixer over a whole sequence, the scan one position
    after another from a zero state."""
    di = c["expand"] * c["d_model"]
    xz = x @ _w(p, "in_proj", x)
    xs, z = xz[..., :di], xz[..., di:]
    xs = jnp.pad(xs, ((0, 0), (c["d_conv"] - 1, 0), (0, 0)))
    u = silu(_conv(p, xs, c))
    delta, bm, cm, a = _ssm_inputs(p, u, c)

    def step(s, inp):
        d_t, b_t, c_t, u_t = inp
        s = jnp.exp(d_t[..., None] * a) * s + \
            (d_t * u_t)[..., None] * b_t[:, None, :]
        return s, (s * c_t[:, None, :]).sum(-1)

    s0 = jnp.zeros((x.shape[0], di, c["d_state"]), x.dtype)
    tm = lambda t: jnp.swapaxes(t, 0, 1)  # noqa: E731 (time-major)
    _, ys = jax.lax.scan(step, s0, (tm(delta), tm(bm), tm(cm), tm(u)))
    y = tm(ys) + u * _w(p, "D", x)
    return (y * silu(z)) @ _w(p, "out_proj", x)


def swiglu(x, w_gate, w_up, w_down):
    return (silu(x @ w_gate) * (x @ w_up)) @ w_down


def ffn(p, x, c=None):
    return swiglu(x, _w(p, "w_gate", x), _w(p, "w_up", x),
                  _w(p, "w_down", x))


def route(p, x, c):
    """Top-k experts of each token and their softmax weights (not
    renormalized): (B, S, E) weights, zero off the top-k."""
    probs = jax.nn.softmax(x @ _w(p, "router", x), axis=-1)
    topv, topi = jax.lax.top_k(probs, c["top_k"])
    return (jax.nn.one_hot(topi, c["n_experts"], dtype=x.dtype)
            * topv[..., None]).sum(-2)


def routing_ties(p, x, c, mantissa_bits: int = 8):
    """Tokens whose top-k expert set a router computing its logits in a
    float of ``mantissa_bits`` significant bits (bfloat16: 8) may pick
    otherwise: the k-th and (k+1)-th largest logits lie within the two
    roundings' reach (half a unit in the last place each) of each
    other. (B, S) bool."""
    z = x @ _w(p, "router", x)
    top = jax.lax.top_k(z, c["top_k"] + 1)[0]
    zk, zn = top[..., -2], top[..., -1]
    reach = 2.0 ** (1 - mantissa_bits) * jnp.maximum(jnp.abs(zk),
                                                     jnp.abs(zn))
    return zk - zn <= reach + 1e-6


def moe(p, x, c, first_expert: int = 0):
    """The held experts' part of the MoE output, dropless: every held
    expert runs on every token, weighted by its routing weight (zero
    where the token did not pick it). ``first_expert`` may be traced."""
    n = p["w_gate"].shape[0]
    gates = jax.lax.dynamic_slice_in_dim(route(p, x, c), first_expert, n,
                                         axis=-1)
    h = silu(jnp.einsum("bsd,edf->bsef", x, _w(p, "w_gate", x))) * \
        jnp.einsum("bsd,edf->bsef", x, _w(p, "w_up", x))
    y = jnp.einsum("bsef,efd->bsed", h, _w(p, "w_down", x))
    return jnp.einsum("bse,bsed->bsd", gates, y)


LAYERS: Dict[str, Callable] = {"attention": attention, "mamba": mamba,
                               "moe": moe, "ffn": ffn}


def period(pp, x, c):
    """One period of pre-norm residual layers. ``pp`` holds ``attn``,
    the Mamba mixers, MoE and dense FFN layers stacked in layer order
    (``mamba``, ``moe``, ``ffn``), and the norms ``ln1``/``ln2`` of each
    layer."""
    take = lambda tree, i: jax.tree.map(lambda a: a[i], tree)  # noqa
    n = {"mamba": 0, "moe": 0, "ffn": 0}
    h = x
    for slot, (mixer, ff) in enumerate(layer_kinds(c)):
        a_in = rms_norm(h, pp["ln1"][slot], c["eps"])
        if mixer == "attention":
            h = h + attention(pp["attn"], a_in, c)
        else:
            h = h + mamba(take(pp["mamba"], n["mamba"]), a_in, c)
            n["mamba"] += 1
        f_in = rms_norm(h, pp["ln2"][slot], c["eps"])
        h = h + LAYERS[ff](take(pp[ff], n[ff]), f_in, c)
        n[ff] += 1
    return h


def decode_last(layer: Callable, p, x, c, **kw):
    """A decode step at the last position: the last row of the full
    forward pass."""
    return layer(p, x, c, **kw)[:, -1:]


# ------------------------------------------------- weights (the check)
def init(key, layer: str, c: dict, n_experts: Optional[int] = None,
         dtype=jnp.bfloat16) -> dict:
    """Seeded random weights of one layer at the widths of ``c``, in the
    pytree the program's layers take: matrices N(0, 1/fan_in) stored in
    ``dtype``; conv bias, dt bias, A_log, D and the norms in float32 at
    values of the scale a trained Mamba has."""
    d, hd = c["d_model"], c["head_dim"]
    nq, nkv = c["n_heads"], c["n_kv_heads"]

    def mat(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32)
                / np.sqrt(fan_in)).astype(dtype)

    ks = list(jax.random.split(key, 12))
    if layer == "attention":
        return {"wq": mat(ks[0], (d, nq, hd), d),
                "wk": mat(ks[1], (d, nkv, hd), d),
                "wv": mat(ks[2], (d, nkv, hd), d),
                "wo": mat(ks[3], (nq, hd, d), nq * hd)}
    if layer == "ffn":
        f = c["d_ff"]
        return {"w_gate": mat(ks[0], (d, f), d), "w_up": mat(ks[1], (d, f), d),
                "w_down": mat(ks[2], (f, d), f)}
    if layer == "moe":
        e, f = n_experts or c["n_experts"], c["d_ff"]
        return {"router": mat(ks[0], (d, c["n_experts"]), d),
                "w_gate": mat(ks[1], (e, d, f), d),
                "w_up": mat(ks[2], (e, d, f), d),
                "w_down": mat(ks[3], (e, f, d), f)}
    di, n, r = c["expand"] * d, c["d_state"], c["dt_rank"]
    u = jax.random.uniform
    return {
        "in_proj": mat(ks[0], (d, 2 * di), d),
        "conv_w": mat(ks[1], (c["d_conv"], di), c["d_conv"]),
        "conv_b": 0.1 * jax.random.normal(ks[2], (di,)),
        "x_proj": mat(ks[3], (di, r + 2 * n), di),
        "dt_proj": mat(ks[4], (r, di), r),
        "dt_bias": jnp.log(jnp.expm1(jnp.exp(u(
            ks[5], (di,), minval=np.log(1e-3), maxval=np.log(1e-1))))),
        "A_log": jnp.log(jnp.tile(jnp.arange(1, n + 1, dtype=jnp.float32),
                                  (di, 1))),
        "D": 1.0 + 0.1 * jax.random.normal(ks[6], (di,)),
        "out_proj": mat(ks[7], (di, d), di),
        "dt_norm": 1.0 + 0.1 * jax.random.normal(ks[8], (r,)),
        "b_norm": 1.0 + 0.1 * jax.random.normal(ks[9], (n,)),
        "c_norm": 1.0 + 0.1 * jax.random.normal(ks[10], (n,)),
    }


def round_float8(x):
    """``x`` (float32) rounded to the nearest float8 e4m3 value (3
    mantissa bits, exponents down to 2**-6, subnormals to 2**-9,
    largest 448), ties to even, computed in float32 arithmetic so that
    any backend runs it."""
    x = jnp.asarray(x, jnp.float32)
    u = jax.lax.bitcast_convert_type(x, jnp.uint32)
    u = (u + jnp.uint32(0x7FFFF) + ((u >> 20) & 1)) & jnp.uint32(0xFFF00000)
    normal = jax.lax.bitcast_convert_type(u, jnp.float32)
    tiny = jnp.round(x * 512.0) / 512.0
    y = jnp.where(jnp.abs(x) < 2.0 ** -6, tiny, normal)
    return jnp.clip(y, -448.0, 448.0)


def to_f32(tree):
    return jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), tree)


def rel_gap(got, want) -> float:
    """||got - want|| / ||want|| (float64 on the host)."""
    g = np.asarray(got, np.float64)
    w = np.asarray(want, np.float64)
    return float(np.linalg.norm(g - w) / max(np.linalg.norm(w), 1e-30))


# ------------------------------------------------- the cell's programs
def _decode_attention(c):
    def step(p, x, k_cache, v_cache, index):
        """One token per row against a cache of L positions (B, L,
        Hkv, D), written at ``index``; positions after it are masked."""
        q, k, v = _project(p, x, c)
        k_cache = jax.lax.dynamic_update_slice(k_cache, k, (0, index, 0, 0))
        v_cache = jax.lax.dynamic_update_slice(v_cache, v, (0, index, 0, 0))
        o = _attend(q, k_cache, v_cache, index[None],
                    jnp.arange(k_cache.shape[1]), c)
        return jnp.einsum("bshk,hkd->bsd", o, _w(p, "wo", x)), k_cache, \
            v_cache
    return step


def _decode_mamba(c):
    def step(p, x, conv_state, ssm_state):
        """One token per row from the conv window (B, k-1, di) and the
        SSM state (B, di, N)."""
        di = c["expand"] * c["d_model"]
        xz = x @ _w(p, "in_proj", x)
        xs, z = xz[..., :di], xz[..., di:]
        window = jnp.concatenate([conv_state, xs], axis=1)
        u = silu(_conv(p, window, c))
        delta, bm, cm, a = _ssm_inputs(p, u, c)
        s = jnp.exp(delta[:, 0, :, None] * a) * ssm_state + \
            (delta[:, 0] * u[:, 0])[..., None] * bm[:, 0, None, :]
        y = (s * cm[:, 0, None, :]).sum(-1)[:, None] + u * _w(p, "D", x)
        out = (y * silu(z)) @ _w(p, "out_proj", x)
        return out, window[:, 1:], s
    return step


def program(layer: str, kind: str, batch: int, length: int, c: dict,
            dtype=jnp.bfloat16) -> Tuple[Callable, tuple]:
    """``(fn, arg_specs)`` of one layer program, all experts held:
    ``train`` the forward and backward (``jax.vjp`` against an output
    cotangent) over ``batch`` x ``length`` positions, ``prefill`` the
    forward, ``decode`` one token per row against a cache of ``length``
    positions (attention) or the conv and SSM state (Mamba); the MoE
    and FFN decode steps, and Mamba's, do not depend on ``length``.

    These are the reference's own forms, not a tuned deployment's: the
    MoE runs all 16 experts densely on every token and weights them by
    the routing (8x the operations of a top-2 dispatch, with no
    ``top_k`` gather or grouped products), attention is the plain
    softmax over every position, and the Mamba scan is one loop over
    positions. A compiler tuning Jamba2-Mini sends routed, blocked and
    chunked programs instead; the pool stands for them by layer, kind
    and shape, not by the ops they hold."""
    p = jax.eval_shape(lambda: init(jax.random.PRNGKey(0), layer, c,
                                    dtype=dtype))
    p = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, dtype), p)
    d = c["d_model"]

    def spec(*shape, dt=dtype):
        return jax.ShapeDtypeStruct(shape, dt)

    fwd = lambda pp, x: LAYERS[layer](pp, x, c)  # noqa: E731
    if kind == "prefill":
        return fwd, (p, spec(batch, length, d))
    if kind == "train":
        def train(pp, x, dy):
            y, pull = jax.vjp(fwd, pp, x)
            return y, pull(dy)
        return train, (p, spec(batch, length, d), spec(batch, length, d))
    x = spec(batch, 1, d)
    if layer == "attention":
        kv = spec(batch, length, c["n_kv_heads"], c["head_dim"])
        return _decode_attention(c), (p, x, kv, kv, spec(dt=jnp.int32))
    if layer == "mamba":
        di = c["expand"] * d
        return _decode_mamba(c), (p, x, spec(batch, c["d_conv"] - 1, di),
                                  spec(batch, di, c["d_state"]))
    return fwd, (p, x)


def lower(layer: str, kind: str, batch: int, length: int, c: dict) -> str:
    fn, specs = program(layer, kind, batch, length, c)
    return jax.jit(fn).lower(*specs).as_text()


def grid(layers: Sequence[str], kinds: Sequence[str],
         batches: Sequence[int], lengths: Sequence[int]
         ) -> List[Tuple[str, str, int, int]]:
    """(layer, kind, batch, length) of every program of the cell's pool:
    the MoE and FFN decode steps once per batch (at the first length),
    since they read no cache."""
    out = []
    for layer in layers:
        for kind in kinds:
            for b in batches:
                for n in lengths:
                    if (kind == "decode" and layer in ("moe", "ffn")
                            and n != lengths[0]):
                        continue
                    out.append((layer, kind, b, n))
    return out


# ------------------------------------ the reference's reading of a text
# The xpu dialect's names for StableHLO ops it calls otherwise; every
# other op is expected under its own name.
XPU_NAMES = {"dot_general": "matmul", "convolution": "conv2d",
             "subtract": "sub", "multiply": "mult", "divide": "div",
             "exponential": "exp", "negate": "neg", "logistic": "sigmoid",
             "broadcast_in_dim": "broadcast", "concatenate": "concat"}
REDUCTIONS = {"add": "reduce_sum", "maximum": "reduce_max",
              "minimum": "reduce_min", "multiply": "reduce_prod"}
_DEF = re.compile(r'^\s*%[^=]*?=\s*"?(\w+)\.(\w+)"?(.*)$')
_CALLS = re.compile(r"\bcall\s+@([\w.$\-]+)")
_FUNC = re.compile(r"^\s*func\.func\b[^@]*@([\w.$\-]+)")


def _expected_name(op: str, rest: str) -> str:
    if op == "reduce":
        m = re.search(r"applies\s+\w+\.(\w+)", rest)
        if m:
            return REDUCTIONS.get(m.group(1), "reduce_" + m.group(1))
    return XPU_NAMES.get(op, op)


def _trip_count(lines: Sequence[str], at: int, before: Sequence[str]
                ) -> Optional[int]:
    """The trip count of the loop at ``lines[at]``: a counter from a
    constant start, compared ``LT`` with a constant bound in ``cond``,
    stepped by a constant ``add`` in ``do``; None otherwise."""
    consts = {}
    for ln in before:
        m = re.match(r"\s*%(\w+) = stablehlo\.constant dense<(-?\d+)> : "
                     r"tensor<i\d+>", ln)
        if m:
            consts[m.group(1)] = int(m.group(2))
    pairs = dict(re.findall(r"%(\w+) = %(\w+)", lines[at]))
    depth, region = 0, []
    for ln in lines[at + 1:]:
        depth += ln.count("{") - ln.count("}")
        region.append(ln)
        if depth <= 0 and "}" in ln and not ln.strip().endswith("{"):
            break
    text = "\n".join(region)
    cond, _, body = text.partition("} do {")
    m = re.search(r"compare\s+LT,\s*%(\w+),\s*%(\w+)", cond)
    if m is None or m.group(1) not in pairs:
        return None
    local = dict(re.findall(r"%(\w+) = stablehlo\.constant dense<(-?\d+)>",
                            cond))
    bound = local.get(m.group(2))
    start = consts.get(pairs[m.group(1)])
    steps = dict(re.findall(r"%(\w+) = stablehlo\.constant dense<(-?\d+)>",
                            body))
    inc = re.search(r"stablehlo\.add %" + m.group(1) + r", %(\w+)", body)
    if bound is None or start is None or inc is None:
        return None
    step = int(steps.get(inc.group(1), consts.get(inc.group(1), 0)))
    if step <= 0:
        return None
    return max(0, -(-(int(bound) - start) // step))


def text_ops(text: str) -> Tuple[Counter, List[Optional[int]]]:
    """The ops a lossless reading of ``text`` keeps, by the name it keeps
    them under, with every call replaced by its callee's ops, and the
    trip count of every loop (None where it is not static)."""
    funcs: Dict[str, List[str]] = {}
    cur = None
    for ln in text.splitlines():
        m = _FUNC.match(ln)
        if m:
            cur = funcs.setdefault(m.group(1), [])
        elif cur is not None:
            cur.append(ln)
    memo: Dict[str, Tuple[Counter, List[Optional[int]]]] = {}

    def count(name: str):
        if name in memo:
            return memo[name]
        memo[name] = (Counter(), [])        # a recursive call adds none
        ops, trips = Counter(), []
        lines = funcs[name]
        for i, ln in enumerate(lines):
            call = _CALLS.search(ln)
            if call and ("= call" in ln or "= func.call" in ln
                         or ln.strip().startswith(("call", "func.call"))):
                if call.group(1) in funcs:
                    sub_ops, sub_trips = count(call.group(1))
                    ops.update(sub_ops)
                    trips.extend(sub_trips)
                continue
            m = _DEF.match(ln)
            if m is None:
                continue
            ops[_expected_name(m.group(2), m.group(3))] += 1
            if m.group(1) == "stablehlo" and m.group(2) == "while":
                trips.append(_trip_count(lines, i, lines[:i]))
        memo[name] = (ops, trips)
        return memo[name]

    entry = "main" if "main" in funcs else next(iter(funcs), None)
    return count(entry) if entry is not None else (Counter(), [])


def lossy_ops(text: str, graph) -> int:
    """Ops of ``text`` missing from ``graph`` or kept under another name
    (the multiset difference), plus loops of ``text`` whose trip count
    the graph does not carry. Reads the graph's ``ops``, their
    ``opcode`` and a loop's ``attrs["trips"]``."""
    want, trips = text_ops(text)
    got = Counter(op.opcode for op in graph.ops)
    missing = sum(max(0, n - got[k]) for k, n in want.items())
    got_trips = Counter(op.attrs.get("trips") for op in graph.ops
                        if op.opcode == "while")
    want_trips = Counter(trips)
    lost = sum(max(0, n - got_trips[t]) for t, n in want_trips.items())
    return missing + lost
