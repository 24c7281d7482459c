"""Compile the main path for a described TPU v5e chip (no chip needed).

The TPU compiler is installed with JAX and compiles for a topology that
is described, not attached, so these tests catch what interpret mode
cannot: Mosaic refusing a kernel (an unsupported primitive, a block
shape off the tiling, too much VMEM) and XLA refusing a program. Each
kernel case asserts ``tpu_custom_call`` in the compiled program, which
shows the Pallas kernel itself was lowered (not interpret mode, not a
reference).

This is the only file that describes the chip. The topology is
described inside a module-scoped fixture, never at import: only one
process at a time may load the TPU library, and pytest-xdist workers
all import every test file.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (AxisType, Mesh, NamedSharding,
                          PartitionSpec as P, SingleDeviceSharding)

from repro.configs.costmodel import COSTMODEL_100M, COSTMODEL_BASE
from repro.core import models as CM
from repro.core import trainer as TR
from repro.kernels import ops as KOPS
from repro.optim import adamw

CFG = COSTMODEL_BASE
MAX_BUCKET = CFG.max_seq        # the serving ladder's largest bucket
MAX_BATCH = 256                 # CostModelService's default max_batch
DTYPES = {"f32": jnp.float32, "bf16": jnp.bfloat16}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a described-chip compile is written to the persistent cache but
    # cannot be read back without the chip: keep the cache off here
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _abstract(tree, sharding, dtype=None):
    """ShapeDtypeStructs on the described chip; floating leaves take
    ``dtype`` when given (the service's bf16 cast)."""
    def leaf(a):
        dt = a.dtype
        if dtype is not None and jnp.issubdtype(dt, jnp.floating):
            dt = dtype
        return jax.ShapeDtypeStruct(a.shape, dt, sharding=sharding)
    return jax.tree.map(leaf, tree)


def _params(kind):
    init = CM.get_model(kind)[0]
    return jax.eval_shape(
        lambda: init(jax.random.PRNGKey(0), CFG, heads=CM.DEFAULT_HEADS))


def _ids(sharding, batch=MAX_BATCH, seq=MAX_BUCKET):
    return jax.ShapeDtypeStruct((batch, seq), jnp.int32, sharding=sharding)


@pytest.mark.parametrize("kind,forward", [
    ("conv1d", KOPS.conv_forward_apply),
    ("lstm", KOPS.lstm_forward_apply)])
@pytest.mark.parametrize("dtype", sorted(DTYPES))
def test_kernel_forward_compiles_for_v5e(one_chip, kind, forward, dtype):
    """The use_kernel serving forward (conv: XLA gather + fused tower/FC/
    heads kernel; lstm: XLA projection + VMEM-carry recurrence kernel,
    hidden 128) at COSTMODEL_BASE widths on the largest bucket."""
    params = _abstract(_params(kind), one_chip, DTYPES[dtype])
    compiled = jax.jit(
        lambda p, ids: forward(p, ids, interpret=False)).lower(
            params, _ids(one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_lstm_kernel_hidden_128(one_chip):
    """The recurrence kernel alone at hidden 128, the BASE width."""
    from repro.kernels.lstm_scan import lstm_scan_fused
    H = CFG.lstm_hidden
    assert H == 128
    xw = jax.ShapeDtypeStruct((64, MAX_BUCKET, 4 * H), jnp.float32,
                              sharding=one_chip)
    mask = jax.ShapeDtypeStruct((64, MAX_BUCKET), jnp.float32,
                                sharding=one_chip)
    wh = jax.ShapeDtypeStruct((H, 4 * H), jnp.float32, sharding=one_chip)
    compiled = jax.jit(lambda a, m, w: lstm_scan_fused(a, m, w)).lower(
        xw, mask, wh).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("kind", ["conv1d", "lstm"])
def test_xla_serving_forward_compiles_for_v5e(one_chip, kind):
    """The plain-XLA serving apply (use_kernel=False) at BASE widths."""
    apply_fn = CM.get_model(kind)[1]
    params = _abstract(_params(kind), one_chip)
    compiled = jax.jit(apply_fn).lower(params, _ids(one_chip)).compile()
    assert "tpu_custom_call" not in compiled.as_text()
    assert compiled.memory_analysis() is not None


def test_train_step_compiles_for_v5e(one_chip):
    """One COSTMODEL_BASE TrainEngine step (the engine's own program)
    for conv1d, three joint heads, batch 64 at the 128 bucket. Its op
    metadata carries the step's name and the model's named scopes, which
    is how a profile of the chip attributes device time."""
    _, apply_fn, _ = CM.get_model("conv1d")
    params = _params("conv1d")
    state = _abstract(
        (params, jax.eval_shape(adamw.init_state, params), None), one_chip)
    opt_cfg = adamw.AdamWConfig(lr=1e-3, total_steps=40, warmup_steps=4)
    step = TR.make_train_step(apply_fn, opt_cfg, CM.DEFAULT_HEADS)
    y = jax.ShapeDtypeStruct((64, len(CM.DEFAULT_HEADS)), np.float32,
                             sharding=one_chip)
    compiled = step.lower(state, _ids(one_chip, 64, 128), y).compile()
    assert compiled.memory_analysis() is not None
    ops = re.findall(r'op_name="([^"]*)"', compiled.as_text())
    scopes = [f"conv{i}" for i in range(len(CFG.conv_channels))]
    for name in [*scopes, "pool", "heads"]:     # jvp(conv0)/..., etc.
        assert any(re.match(rf"jit\(train_step\)/.*\b{name}\b", o)
                   for o in ops), name


def test_chunk_programs_compile_for_v5e(one_chip):
    """A batch of 64 split into four width-sorted chunks: the gradient
    program of one (16, 128) chunk and the program that sums the four
    chunks' gradients and applies them, for COSTMODEL_BASE conv1d with
    three joint heads."""
    _, apply_fn, _ = CM.get_model("conv1d")
    params = _params("conv1d")
    heads = CM.DEFAULT_HEADS
    p = _abstract(params, one_chip)
    y = jax.ShapeDtypeStruct((64, len(heads)), np.float32,
                             sharding=one_chip)
    grad = TR.chunk_grad.lower(apply_fn, heads, p, _ids(one_chip, 16, 128),
                               y, 16)
    assert re.search(r'op_name="jit\(chunk_grad\)/.*\bconv0\b',
                     grad.compile().as_text())
    chunk = _abstract(jax.eval_shape(
        lambda: TR.chunk_grad(apply_fn, heads, params,
                              jnp.zeros((16, 128), jnp.int32),
                              jnp.zeros((64, len(heads))), 16)), one_chip)
    opt_cfg = adamw.AdamWConfig(lr=1e-3, total_steps=40, warmup_steps=4)
    state = _abstract(
        (params, jax.eval_shape(adamw.init_state, params), None), one_chip)
    update = TR.apply_grads.lower(opt_cfg, state, (chunk,) * 4).compile()
    assert update.memory_analysis() is not None


def _flops(compiled):
    cost = compiled.cost_analysis()
    return (cost[0] if isinstance(cost, list) else cost)["flops"]


def _elements(shapes):
    """Element counts of the array shapes in an HLO result type."""
    return [int(np.prod([int(d) for d in dims.split(",") if d]))
            for dims in re.findall(r"\w+\[([\d,]*)\]", shapes)]


def test_data_parallel_chunk_splits_rows_for_v5e(topo, one_chip):
    """A ``mesh_data=4`` engine's chunk program at ``COSTMODEL_100M``
    (a 16x1024 chunk of a batch of 64, three heads) on a 2x2 v5e: each
    chip computes its own 4 rows, at most 0.3x the FLOPs of the whole
    chunk on one chip, gathers nothing larger than the labels and sums
    nothing across chips; the update exchanges the gradient once. The
    CPU partitioner splits the rows under any parameter placement; the
    TPU compiler replicates every convolution on every chip when the
    embedding table is split by features."""
    cfg, heads = COSTMODEL_100M, CM.DEFAULT_HEADS
    eng = TR.TrainEngine("conv1d", cfg, heads, mesh_data=4)
    params = jax.eval_shape(
        lambda: eng.init_fn(jax.random.PRNGKey(0), cfg, heads=heads))
    mesh = Mesh(np.asarray(topo.devices).reshape(4, 1), ("data", "model"),
                axis_types=(AxisType.Auto, AxisType.Auto))
    shardings = eng.param_shardings(mesh, params)
    assert all("data" not in str(s.spec)
               for s in jax.tree.leaves(shardings))
    rows = NamedSharding(mesh, P("data"))
    labels = (64, len(heads))
    placed = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        params, shardings)
    split = TR.chunk_grad.lower(
        eng.apply_fn, heads, placed, _ids(rows, 16, 1024),
        jax.ShapeDtypeStruct(labels, np.float32, sharding=rows),
        0).compile()
    whole = TR.chunk_grad.lower(
        eng.apply_fn, heads, _abstract(params, one_chip),
        _ids(one_chip, 16, 1024),
        jax.ShapeDtypeStruct(labels, np.float32, sharding=one_chip),
        0).compile()
    assert _flops(split) <= 0.3 * _flops(whole), \
        (_flops(split), _flops(whole))
    gathers = re.findall(r"= (.*?) all-gather(?:-start)?\(",
                         split.as_text())
    assert all(n <= np.prod(labels) for g in gathers
               for n in _elements(g)), gathers
    # the chips keep their partial gradients; the update sums them in
    # one exchange of the whole gradient (and the loss) a step
    assert "all-reduce" not in split.as_text()
    chunk = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rows),
        jax.eval_shape(lambda: TR.chunk_grad(
            eng.apply_fn, heads, placed, _ids(rows, 16, 1024),
            jax.ShapeDtypeStruct(labels, np.float32, sharding=rows), 0)))
    state = _abstract(
        (params, jax.eval_shape(adamw.init_state, params), None),
        NamedSharding(mesh, P()))
    opt_cfg = adamw.AdamWConfig(lr=1e-3, total_steps=40, warmup_steps=4)
    update = TR.apply_grads.lower(opt_cfg, state, (chunk,) * 4).compile()
    exchanged = sum(sum(_elements(r)) for r in re.findall(
        r"= (.*?) all-reduce(?:-start)?\(", update.as_text()))
    n_params = sum(p.size for p in jax.tree.leaves(params))
    assert n_params <= exchanged <= n_params + 1, (exchanged, n_params)
