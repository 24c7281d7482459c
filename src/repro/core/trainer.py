"""Supervised training for the cost models (paper §3-4) — one engine.

:class:`TrainEngine` owns the repo's ONE training step loop. Every caller
— the quickstart example, the serve/benchmark drivers, the production
``launch/train.py`` CLI, and the :func:`train_model` compatibility wrapper
— builds an engine and calls :meth:`TrainEngine.fit`. The engine wires the
full substrate every time:

* a sharded, prefetching :class:`repro.data.pipeline.Loader` (deterministic,
  resumable cursor), **bucket-aware** by default: batches are grouped by
  power-of-two sequence bucket (the same ladder serving uses, including the
  conv1d pad-slack rule), so each train step jits one program per bucket
  instead of padding every batch to the global ``max_seq``;
* width-sorted chunks: a batch whose rows span several buckets arrives
  as :class:`~repro.data.pipeline.Chunks` (rows sorted by bucket, four
  sub-batches each at its own widest bucket). The step runs one gradient
  program per chunk shape, each giving its row-weighted gradient and
  loss, then one program that sums them and compresses, clips and
  applies AdamW: the whole batch's update over fewer pad positions.
  Every chunk shape is compiled before step 1. A batch the loader
  leaves whole runs the one fused ``train_step`` program;
* data parallelism on a mesh of more than one device: every parameter
  (and so the AdamW moments) is whole on each chip of the ``data`` axis
  and each batch is split there by rows, so each chip computes its own
  rows' gradient (:meth:`TrainEngine.param_shardings`; the ``model``
  axis keeps the per-family logical axis tables of
  :class:`~repro.runtime.sharding.ShardingRules`). A split batch's
  chunk programs keep each chip's partial gradient and ``apply_grads``
  sums chunks and chips in one exchange a step;
* optional int8 error-feedback gradient compression on the DP axis;
* a :class:`~repro.runtime.fault.TrainSupervisor` step loop: periodic +
  on-preemption atomic checkpoints carrying the loader cursor, and
  automatic resume — or, with ``ckpt_dir=None``, the same loop with
  persistence disabled.

Metrics match the paper: relative RMSE ("5-7% range") and %-exact for
register pressure (Fig. 6: ~75% exact).

``target`` may be a single name (legacy scalar head) or a sequence of
names, which trains one shared encoder with a per-target head dict under
a joint MSE (mean of per-target MSEs in normalized space). Multi-target
results carry per-target ``norm_stats`` and ``evaluate`` reports metrics
per target.
"""
from __future__ import annotations

import dataclasses
import time
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Callable, Dict, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

from repro.core import models as CM
from repro.data import pipeline as PIPE
from repro.ir import dataset as DS
from repro.obs.trace import OFF, annotate, annotate_step, profiling
from repro.optim import adamw, compress
from repro.runtime import fault
from repro.runtime.sharding import ShardingRules, tree_shardings

TargetSpec = Union[str, Sequence[str]]


@dataclass
class TrainResult:
    params: Any
    stats: Dict[str, float]
    history: list = field(default_factory=list)
    # single-target: {"mu": ..., "sigma": ...}; multi-target: {target: {...}}
    norm_stats: Dict[str, Any] = field(default_factory=dict)
    heads: Optional[Tuple[str, ...]] = None


def make_loss_fn(apply_fn, heads: Optional[Tuple[str, ...]] = None):
    """MSE loss. With ``heads``, ``y`` is (B, n_heads) column-per-target
    and the loss is the mean of per-target MSEs (joint training)."""
    def loss_fn(params, ids, y):
        pred = apply_fn(params, ids)
        if heads:
            per = [jnp.mean(jnp.square(pred[t] - y[:, i]))
                   for i, t in enumerate(heads)]
            return jnp.mean(jnp.stack(per))
        return jnp.mean(jnp.square(pred - y))
    return loss_fn


def _update(opt_cfg, state, grads):
    params, opt_state, err = state
    if err is not None:
        grads, err = compress.compress_grads(grads, err)
    params, opt_state, _ = adamw.apply_updates(
        params, grads, opt_state, opt_cfg)
    return params, opt_state, err


# The programs are module-level with the model and optimizer as static
# arguments, so every step built for the same model shares their
# compiled executables: the engine compiles the chunk programs before
# step 1 and the step it hands out finds them.
@partial(jax.jit, static_argnums=(0, 1, 2))
def train_step(apply_fn, opt_cfg, heads, state, ids, yy):
    """One whole batch: gradient, compression, clipping, AdamW."""
    loss, grads = jax.value_and_grad(make_loss_fn(apply_fn, heads))(
        state[0], ids, yy)
    return _update(opt_cfg, state, grads), loss


@partial(jax.jit, static_argnums=(0, 1))
def chunk_grad(apply_fn, heads, params, ids, yy, start):
    """One chunk's float32 gradient and loss, each weighted by the
    chunk's share of the batch's rows; the chunk's labels are
    ``yy[start:start + rows]``. On a mesh whose ``data`` axis holds n > 1
    devices each device keeps the gradient of its own rows: gradient and
    loss then carry a leading axis of the n partials, split along
    ``data``, and :func:`apply_grads` sums them."""
    rows = ids.shape[0]
    y = jax.lax.dynamic_slice_in_dim(yy, start, rows)
    grad_fn = jax.value_and_grad(make_loss_fn(apply_fn, heads))

    def weighted(params, ids, y, w):
        loss, grads = grad_fn(params, ids, y)
        return (jax.tree.map(lambda g: w * g.astype(jnp.float32), grads),
                w * loss)

    w = rows / yy.shape[0]
    mesh = jax.typeof(ids).sharding.mesh
    n = 1 if mesh.empty else mesh.shape.get("data", 1)
    if n == 1:
        return weighted(params, ids, y, w)

    def local(params, ids, y):          # a device's rows: 1/n of the chunk
        grads, loss = weighted(params, ids, y, w / n)
        return jax.tree.map(lambda g: g[None], grads), loss[None]
    # check_vma off: the families' scans start from unvarying zeros
    return jax.shard_map(local, mesh=mesh, axis_names={"data"},
                         in_specs=(P(), P("data"), P("data")),
                         out_specs=P("data"), check_vma=False)(
                             params, ids, y)


@partial(jax.jit, static_argnums=0)
def apply_grads(opt_cfg, state, chunks):
    """The whole batch's update and loss from its chunks' weighted
    gradients and losses, summed here: over the chunks, then over the
    devices' partials where the chunks carry them, so a mesh exchanges
    gradients once a step."""
    grads = jax.tree.map(lambda *g: sum(g), *[g for g, _ in chunks])
    split = chunks[0][1].ndim > 0
    if split:
        grads = jax.tree.map(lambda g: g.sum(0), grads)
    state = _update(opt_cfg, state, grads)
    loss = sum(loss for _, loss in chunks)
    return state, loss.sum() if split else loss


class TrainStep:
    """The engine's step: ``(state, ids, y) -> (state, loss)`` with
    ``state = (params, opt_state, err)``. ``err`` is the int8
    error-feedback state of gradient compression, or None when
    compression is off. ``ids`` is an array (one ``train_step``
    program) or :class:`~repro.data.pipeline.Chunks` (a ``chunk_grad``
    program a chunk, then ``apply_grads``); ``y`` is one array in the
    batch's row order."""

    def __init__(self, apply_fn, opt_cfg, heads=None):
        self.static = (apply_fn, opt_cfg, heads)

    def __call__(self, state, ids, yy):
        apply_fn, opt_cfg, heads = self.static
        if not isinstance(ids, PIPE.Chunks):
            return train_step(apply_fn, opt_cfg, heads, state, ids, yy)
        chunks, start = [], 0
        for part in ids.parts:
            chunks.append(chunk_grad(apply_fn, heads, state[0], part, yy,
                                     start))
            start += part.shape[0]
        return apply_grads(opt_cfg, state, tuple(chunks))

    def lower(self, state, ids, yy):
        """The whole-batch program, lowered (for compile tests)."""
        return train_step.lower(*self.static, state, ids, yy)


def make_train_step(apply_fn, opt_cfg,
                    heads: Optional[Tuple[str, ...]] = None) -> TrainStep:
    """The engine's step (:class:`TrainStep`). Public so a compile test
    can build the exact programs the engine runs."""
    return TrainStep(apply_fn, opt_cfg, heads)


@dataclass(frozen=True)
class EngineConfig:
    """Every knob of the unified step loop (CLI flags map 1:1 onto this)."""
    steps: int = 300
    batch_size: int = 64
    lr: float = 1e-3
    weight_decay: float = 0.01
    seed: int = 0
    log_every: int = 100
    verbose: bool = False
    # batching: per-bucket pad widths (one jitted program per bucket).
    # "batch_max" keeps the global shuffle and is gradient-identical to
    # max_seq padding; "homogeneous" maximizes the step-time win but
    # length-correlates batch composition (see data/pipeline.py).
    bucketed: bool = True
    bucket_mode: str = "batch_max"
    min_bucket: int = 32
    drop_remainder: bool = True
    # mesh / sharding
    mesh_data: int = 1
    mesh_model: int = 1
    # substrate
    compress_grads: bool = False
    ckpt_dir: Optional[str] = None     # None -> loop without persistence
    save_every: int = 100
    keep: int = 3
    check_treedef: bool = True
    install_sigterm: bool = False   # checkpoint + stop on SIGTERM
    shard_index: int = 0
    num_shards: int = 1
    prefetch: int = 2


class TrainEngine:
    """The one way to train a cost model (see module docstring).

    >>> engine = TrainEngine("conv1d", cfg, ("latency_us",), steps=500)
    >>> result = engine.fit(train_ds)
    """

    def __init__(self, kind: str, cfg, target: TargetSpec,
                 engine: Optional[EngineConfig] = None, **overrides):
        self.kind = kind
        self.cfg = cfg
        self.heads = None if isinstance(target, str) else tuple(target)
        self.target = target
        self.ecfg = dataclasses.replace(engine or EngineConfig(),
                                        **overrides)
        self.init_fn, self.apply_fn, self.axes_fn = CM.get_model(kind)
        self.mesh = None                  # the ("data", "model") mesh of fit

    # ------------------------------------------------------------- pipeline
    def bucket_assignments(self, train: DS.CostDataset
                           ) -> Optional[np.ndarray]:
        """Per-row train bucket length, honoring the serving-side pad-slack
        rule (conv1d needs slack so bucketing is prediction-preserving)."""
        if not self.ecfg.bucketed:
            return None
        from repro.core.service import pad_slack
        # ladder from the DATASET width (the unbucketed path feeds ids at
        # dataset width too); a model whose capacity is narrower than the
        # data (e.g. an xformer pos table) fails loudly either way
        buckets = DS.default_buckets(train.max_seq, self.ecfg.min_bucket)
        return DS.bucket_lengths(train.get_seq_lens(), buckets,
                                 pad_slack(self.kind, self.cfg))

    def make_loader(self, train: DS.CostDataset, y: np.ndarray
                    ) -> PIPE.Loader:
        e = self.ecfg
        if train.ids is not None:
            src = PIPE.ArraySource(ids=train.ids, y=y)
        else:
            # bucket-grouped storage: materialize rows on demand at the
            # widest width a batch could need; the Loader trims per bucket
            width = max(train.bucket_ids) if e.bucketed else train.max_seq
            src = PIPE.FnSource(train.n, lambda idx: {
                "ids": train.row_ids(idx, width), "y": y[idx]})
        return PIPE.Loader(src, e.batch_size, seed=e.seed,
                           shard_index=e.shard_index,
                           num_shards=e.num_shards,
                           drop_remainder=e.drop_remainder,
                           prefetch=e.prefetch,
                           bucket_by=self.bucket_assignments(train),
                           bucket_mode=e.bucket_mode,
                           chunk_multiple=e.mesh_data)

    def place_batch(self, batch: Dict[str, Any]) -> Dict[str, Any]:
        """Host batch -> device arrays (a :class:`~repro.data.pipeline.
        Chunks` chunk by chunk). On a multi-device mesh (set by
        :meth:`fit`) every array is split along the mesh's ``data`` axis
        on its leading (batch) dim; on one device it goes to the default
        device."""
        mesh = self.mesh
        if mesh is None or mesh.devices.size == 1:
            put = jnp.asarray
        else:
            sharding = NamedSharding(mesh, P("data"))

            def put(v):
                return jax.device_put(v, sharding)
        return {k: v.map(put) if isinstance(v, PIPE.Chunks) else put(v)
                for k, v in batch.items()}

    def param_shardings(self, mesh, params):
        """Each parameter's sharding on ``mesh``: the model family's
        logical axis tables, with nothing on ``data``. The default rules
        split ``embed`` over ``data`` (FSDP, for the 30-50B language
        models ``launch/dryrun.py`` lowers); a cost model's whole train
        state is under 1 GB, and with its embedding table split by
        features the TPU compiler gathers each chunk's embeddings and
        runs every convolution on all of the chunk's rows on every
        chip."""
        rules = ShardingRules(mesh, overrides={"embed": None})
        axes = self.axes_fn(self.cfg, heads=self.heads) if self.heads \
            else self.axes_fn(self.cfg)
        return tree_shardings(rules, axes,
                              jax.tree.map(lambda x: x.shape, params))

    def compile_chunks(self, loader: PIPE.Loader, state, opt_cfg,
                       y: np.ndarray) -> None:
        """Compile (or load from the persistent cache) every chunk
        program the loader can ask for, by running each once on zeros:
        a ``chunk_grad`` per chunk shape and ``apply_grads``. The
        results are dropped."""
        shapes = loader.chunk_shapes()
        if not shapes:
            return
        yy = self.place_batch({"y": np.zeros(
            (loader.local_batch,) + y.shape[1:], y.dtype)})["y"]
        for rows, width in shapes:
            ids = self.place_batch(
                {"ids": np.zeros((rows, width), np.int32)})["ids"]
            chunk = chunk_grad(self.apply_fn, self.heads, state[0], ids,
                               yy, 0)
        apply_grads(opt_cfg, state, (chunk,) * PIPE.CHUNKS)

    # ------------------------------------------------------------------ fit
    def fit(self, train: DS.CostDataset, *,
            on_step: Optional[Callable] = None) -> TrainResult:
        e = self.ecfg
        key = jax.random.PRNGKey(e.seed)
        if self.heads:
            params = self.init_fn(key, self.cfg, heads=self.heads)
            y, norm_stats = DS.stacked_normalized_targets(train.targets,
                                                          self.heads)
        else:
            params = self.init_fn(key, self.cfg)
            y, norm_stats = DS.normalize_targets(train.targets[self.target])
            y = y.astype(np.float32)
        loader = self.make_loader(train, y)

        # Auto axes: params and batch carry explicit NamedShardings and
        # GSPMD propagates the rest
        mesh = self.mesh = jax.make_mesh(
            (e.mesh_data, e.mesh_model), ("data", "model"),
            axis_types=(AxisType.Auto, AxisType.Auto))
        if mesh.devices.size > 1:
            params = jax.tree.map(jax.device_put, params,
                                  self.param_shardings(mesh, params))

        opt_cfg = adamw.AdamWConfig(lr=e.lr, total_steps=e.steps,
                                    warmup_steps=min(50, e.steps // 10),
                                    weight_decay=e.weight_decay)
        err0 = compress.init_error_state(params) if e.compress_grads \
            else None
        run_step = make_train_step(self.apply_fn, opt_cfg, self.heads)

        sup = fault.TrainSupervisor(e.ckpt_dir, save_every=e.save_every,
                                    keep=e.keep)
        if e.install_sigterm:
            sup.install_signal_handler()
        state = (params, adamw.init_state(params), err0)
        state, start, extra = sup.try_restore(
            state, check_treedef=e.check_treedef)
        if start and "loader" in extra:
            loader.state = PIPE.LoaderState(**extra["loader"])

        it = iter(loader)
        history = []
        last = [jnp.float32(np.nan)]

        def step_fn(state, step):
            # profiler spans: the loader, the host->device copy, the step
            with annotate("train.batch"):
                batch = next(it)
            with annotate("train.place"):
                placed = self.place_batch(batch)
            span = OFF
            if profiling():
                ids = batch["ids"]
                parts = ids.parts if isinstance(ids, PIPE.Chunks) \
                    else (ids,)
                span = annotate_step(   # tokens: non-PAD (id 0) positions
                    "train.step", step, rows=ids.shape[0],
                    width=ids.shape[1],
                    tokens=sum(int(np.count_nonzero(p)) for p in parts),
                    positions=sum(p.size for p in parts),
                    chunks=len(parts))
            with span:
                state, loss = run_step(state, placed["ids"], placed["y"])
            last[0] = loss     # device value; sync only at log points
            return state

        def _on_step(step, dt):
            if step % e.log_every == 0 or step == e.steps:
                history.append((step, float(last[0])))
                if e.verbose:
                    print(f"  step {step}: mse={float(last[0]):.4f} "
                          f"({dt * 1e3:.0f} ms)")
            if on_step is not None:
                on_step(step, dt)

        heads_extra = list(self.heads) if self.heads else [self.target]
        t0 = time.perf_counter()
        with mesh:
            if start < e.steps:
                self.compile_chunks(loader, state, opt_cfg, y)
            state = sup.run(
                state, step_fn, e.steps, start_step=start,
                extra_fn=lambda: {"loader": loader.state.as_dict(),
                                  "norm_stats": norm_stats,
                                  "heads": heads_extra},
                on_step=_on_step)
        wall = time.perf_counter() - t0
        steps_run = max(e.steps - start, 0)
        # a resume that finds the run already complete executes 0 steps:
        # final_loss is then NaN (nothing ran) and steps_per_s 0 by design
        stats = {"final_loss": float(last[0]),
                 "steps": float(steps_run),
                 "start_step": float(start),
                 "wall_time_s": wall,
                 "steps_per_s": steps_run / max(wall, 1e-9)}
        return TrainResult(params=state[0], stats=stats, history=history,
                           norm_stats=norm_stats, heads=self.heads)


def train_model(kind: str, cfg, train: DS.CostDataset, target: TargetSpec,
                *, steps: int = 300, batch_size: int = 64,
                lr: float = 1e-3, seed: int = 0, log_every: int = 100,
                verbose: bool = False, **engine_overrides) -> TrainResult:
    """Compatibility wrapper: a TrainEngine with in-memory defaults."""
    return TrainEngine(kind, cfg, target, steps=steps,
                       batch_size=batch_size, lr=lr, seed=seed,
                       log_every=log_every, verbose=verbose,
                       **engine_overrides).fit(train)


def _target_metrics(pred_n: np.ndarray, true: np.ndarray,
                    stats: Dict[str, float]) -> Dict[str, float]:
    """Paper metrics: relative RMSE (%), normalized RMSE, %-exact (rounded)."""
    pred = DS.denormalize(pred_n, stats)
    rel = (pred - true) / np.maximum(np.abs(true), 1e-6)
    # normalized-space RMSE against the train normalization
    true_n = (np.log1p(true) - stats["mu"]) / stats["sigma"]
    return {
        "rmse_rel_pct": float(np.sqrt(np.mean(np.square(rel))) * 100),
        "mape_pct": float(np.mean(np.abs(rel)) * 100),
        "rmse_norm": float(np.sqrt(np.mean(np.square(pred_n - true_n)))),
        "exact_pct": float(np.mean(np.round(pred) == np.round(true)) * 100),
        "within5_pct": float(np.mean(np.abs(rel) <= 0.05) * 100),
    }


def evaluate(kind: str, cfg, result: TrainResult, test: DS.CostDataset,
             target: Optional[TargetSpec] = None, batch_size: int = 256
             ) -> Dict[str, Any]:
    """Evaluate a TrainResult.

    Single-head result + target name -> flat metrics dict (legacy).
    Multi-head result -> {target: metrics} for every requested target
    (default: all heads); passing a single name returns that head's flat
    metrics dict.
    """
    _, apply_fn, _ = CM.get_model(kind)
    apply_j = jax.jit(apply_fn)
    test_ids = test.dense_ids()
    preds = []
    for i in range(0, len(test_ids), batch_size):
        ids = jnp.asarray(test_ids[i:i + batch_size])
        preds.append(jax.device_get(apply_j(result.params, ids)))
    if result.heads:
        pred_n = {t: np.concatenate([np.asarray(p[t]) for p in preds])
                  for t in result.heads}
        if isinstance(target, str):
            return _target_metrics(pred_n[target], test.targets[target],
                                   result.norm_stats[target])
        wanted = tuple(target) if target is not None else result.heads
        return {t: _target_metrics(pred_n[t], test.targets[t],
                                   result.norm_stats[t])
                for t in wanted}
    if not isinstance(target, str):
        raise ValueError("single-head evaluate needs a target name")
    pred_n = np.concatenate([np.asarray(p) for p in preds])
    return _target_metrics(pred_n, test.targets[target], result.norm_stats)
