"""Input programs for the benchmark's traffic, generated from a seed.

A copy of the sampler families the cost model was built for (Resnet,
BERT, Unet, SSD and Yolo subgraphs with a frequent-shape pool), the
"unoptimized IR" dressing that gives a search a rich rewrite frontier,
and a plain loop unroll. They build the program's ``Graph`` type, which
is the system's input, but share no code with the program's own
generators: a later change to those moves the program, not the traffic.
"""
from __future__ import annotations

import numpy as np

from repro.ir.graph import Graph, Tensor

BATCHES = [1, 8, 16, 32]
SPATIAL = [7, 14, 28, 56, 112, 224]
CHANNELS = [3, 16, 32, 64, 128, 256, 512, 1024]
HIDDEN = [128, 256, 512, 768, 1024, 2048, 4096]
SEQ = [64, 128, 256, 512]
FAMILIES = ("bert", "resnet", "ssd", "unet", "yolo")
ELEMENTWISE = ("abs", "add", "div", "exp", "gelu", "maximum", "minimum",
               "mult", "neg", "relu", "rsqrt", "sigmoid", "silu", "sub",
               "tanh")


def _conv_block(g, rng, x, t, channels):
    c_out = int(rng.choice(channels))
    n, h, w, _ = t.shape
    stride = int(rng.choice([1, 1, 1, 2]))
    out_t = Tensor((n, max(h // stride, 1), max(w // stride, 1), c_out),
                   t.dtype)
    x = g.add_op("conv2d", [x], out_t, stride=stride, kernel=3)
    if rng.random() < 0.7:
        x = g.add_op("batchnorm", [x], out_t)
    x = g.add_op(str(rng.choice(["relu", "silu", "gelu"])), [x], out_t)
    return x, out_t


def _resnet(rng):
    g = Graph(name="resnet_sub")
    s = int(rng.choice(SPATIAL))
    t = Tensor((int(rng.choice(BATCHES)), s, s, int(rng.choice(CHANNELS))))
    x = g.add_arg(t)
    for _ in range(rng.integers(1, 5)):
        skip, skip_t = x, t
        x, t = _conv_block(g, rng, x, t, CHANNELS)
        x2, t2 = _conv_block(g, rng, x, t, [t.shape[-1]])
        if t2.shape == skip_t.shape:
            x, t = g.add_op("add", [x2, skip], t2), t2
        else:
            x, t = x2, t2
    if rng.random() < 0.3:
        n_, h_, w_, c_ = t.shape
        t = Tensor((n_, max(h_ // 2, 1), max(w_ // 2, 1), c_))
        x = g.add_op("pool_max", [x], t)
    g.outputs = [x]
    return g


def _bert(rng):
    g = Graph(name="bert_sub")
    b, s = int(rng.choice(BATCHES)), int(rng.choice(SEQ))
    d = int(rng.choice(HIDDEN))
    ff = int(rng.choice([2 * d, 4 * d]))
    t = Tensor((b, s, d))
    x = g.add_arg(t)
    wq = g.add_arg(Tensor((d, d)))
    wo = g.add_arg(Tensor((d, d)))
    wf1 = g.add_arg(Tensor((d, ff)))
    wf2 = g.add_arg(Tensor((ff, d)))
    for _ in range(rng.integers(1, 4)):
        q = g.add_op("matmul", [x, wq], t)
        k = g.add_op("matmul", [x, wq], t)
        v = g.add_op("matmul", [x, wq], t)
        at = Tensor((b, s, s))
        a = g.add_op("matmul", [q, k], at, transpose_b=True)
        a = g.add_op("softmax", [a], at)
        o = g.add_op("matmul", [a, v], t)
        o = g.add_op("matmul", [o, wo], t)
        x = g.add_op("add", [x, o], t)
        x = g.add_op("layernorm", [x], t)
        h_t = Tensor((b, s, ff))
        h = g.add_op("gelu", [g.add_op("matmul", [x, wf1], h_t)], h_t)
        x = g.add_op("add", [x, g.add_op("matmul", [h, wf2], t)], t)
        x = g.add_op("layernorm", [x], t)
    g.outputs = [x]
    return g


def _unet(rng):
    g = Graph(name="unet_sub")
    s = int(rng.choice([56, 112, 224]))
    t = Tensor((int(rng.choice([1, 2, 4])), s, s,
                int(rng.choice([16, 32, 64]))))
    x = g.add_arg(t)
    skips = []
    for _ in range(int(rng.integers(1, 4))):
        x, t = _conv_block(g, rng, x, t, [t.shape[-1] * 2])
        skips.append((x, t))
        n_, h_, w_, c_ = t.shape
        t = Tensor((n_, max(h_ // 2, 1), max(w_ // 2, 1), c_))
        x = g.add_op("pool_max", [x], t)
    for sx, st in reversed(skips):
        n_, h_, w_, c_ = t.shape
        t_up = Tensor((n_, h_ * 2, w_ * 2, c_))
        x = g.add_op("upsample", [x], t_up)
        if t_up.shape[:3] == st.shape[:3]:
            t = Tensor(t_up.shape[:3] + (t_up.shape[3] + st.shape[3],))
            x = g.add_op("concat", [x, sx], t)
        else:
            t = t_up
        x, t = _conv_block(g, rng, x, t, [st.shape[-1]])
    g.outputs = [x]
    return g


def _detector(rng, name, heads):
    g = Graph(name=name)
    s = int(rng.choice([28, 56, 112]))
    t = Tensor((int(rng.choice([1, 8])), s, s,
                int(rng.choice([64, 128, 256]))))
    x = g.add_arg(t)
    for _ in range(rng.integers(2, 6)):
        x, t = _conv_block(g, rng, x, t, CHANNELS)
    outs = []
    for _ in range(heads):
        n_, h_, w_, _ = t.shape
        box_t = Tensor((n_, h_, w_, int(rng.choice([4, 8, 12]))))
        cls_t = Tensor((n_, h_, w_, int(rng.choice([20, 80, 91]))))
        b = g.add_op("conv2d", [x], box_t, stride=1, kernel=3)
        cl = g.add_op("conv2d", [x], cls_t, stride=1, kernel=3)
        outs += [b, g.add_op("sigmoid", [cl], cls_t)]
    g.outputs = outs
    return g


_SAMPLERS = {
    "bert": _bert,
    "resnet": _resnet,
    "ssd": lambda rng: _detector(rng, "ssd_sub", int(rng.integers(1, 4))),
    "unet": _unet,
    "yolo": lambda rng: _detector(rng, "yolo_sub", int(rng.integers(1, 3))),
}


def sample(rng: np.random.Generator, family: str) -> Graph:
    """One sampled subgraph of ``family``."""
    g = _SAMPLERS[family](rng)
    g.validate()
    return g


def unoptimized(g: Graph, rng: np.random.Generator) -> Graph:
    """Dress a sampled graph as the unoptimized IR a compiler hands its
    optimizer: elementwise chains ending in outputs (fusion fodder),
    duplicated subexpressions (CSE fodder) and dead ops (DCE fodder)."""
    new = Graph(name=g.name + "_raw")
    new.values = list(g.values[:g.n_args])
    new.n_args = g.n_args
    for op in g.ops:
        new.add_op(op.opcode, list(op.operands), g.values[op.result],
                   **op.attrs)
    new.outputs = list(g.outputs)
    results = [op.result for op in new.ops]
    for _ in range(6):
        v = results[int(rng.integers(0, len(results)))]
        for _ in range(int(rng.integers(3, 7))):
            t = new.values[v]
            v = new.add_op(ELEMENTWISE[int(rng.integers(0, len(ELEMENTWISE)))],
                           [v], Tensor(t.shape, t.dtype))
        new.outputs.append(v)
    for _ in range(4):
        op = new.ops[int(rng.integers(0, len(new.ops)))]
        d = new.add_op(op.opcode, list(op.operands),
                       new.values[op.result], **op.attrs)
        t = new.values[d]
        new.outputs.append(new.add_op("relu", [d], Tensor(t.shape, t.dtype)))
    for _ in range(3):
        v = results[int(rng.integers(0, len(results)))]
        t = new.values[v]
        new.add_op("exp", [v], Tensor(t.shape, t.dtype))
    new.validate()
    return new


def unroll(g: Graph, factor: int) -> Graph:
    """The loop body replicated ``factor`` times over shared arguments,
    each replica's SSA ids renamed; every replica's outputs are kept."""
    new = Graph(name=f"{g.name}_x{factor}")
    new.values = list(g.values[:g.n_args])
    new.n_args = g.n_args
    outs = []
    for _ in range(factor):
        ren = {i: i for i in range(g.n_args)}
        for op in g.ops:
            ren[op.result] = new.add_op(
                op.opcode, [ren[o] for o in op.operands],
                g.values[op.result], **op.attrs)
        outs += [ren[o] for o in g.outputs]
    new.outputs = outs
    new.validate()
    return new


def family_of(i: int) -> str:
    return FAMILIES[i % len(FAMILIES)]

