"""Divisibility-aware logical-axis sharding resolver.

Logical tensor axes (``"batch"``, ``"vocab"``, ``"heads"``, ``"ffn"``,
``"experts"``, ``"seq"``, ``"embed"``, ...) are mapped to mesh axes by a rule
table. A mesh axis is *dropped* (falls back to replication for that dim) when
the dimension size is not divisible by the mesh axis size — GSPMD rejects
uneven explicit shardings, and this resolver is what lets one rule table
serve every architecture (e.g. 40 attention heads cannot shard over a 16-way
``model`` axis; the resolver drops it and the context-parallel ``seq`` rule
picks up the parallelism instead).
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AxisRule = Union[None, str, Tuple[str, ...]]

# Default logical->mesh rules. 'pod' composes with 'data' for the batch dim
# so the same table serves single-pod (no 'pod' axis) and multi-pod meshes.
DEFAULT_RULES: Dict[str, Tuple[str, ...]] = {
    # batch spreads over the model axis too when divisible (wide DP):
    # per-layer TP activation collectives would dominate train steps at
    # every model size (0.6B..52B), while weight gathers
    # (FSDP, from the 2D param sharding below) are smaller and overlappable.
    # Smaller batches (prefill 32, decode 128) gracefully fall back to
    # data-only sharding via the divisibility resolver.
    "batch":   ("pod", "data", "model"),
    "vocab":   ("model",),
    "heads":   ("model",),      # q heads
    "kv_heads": ("model",),     # usually dropped (kv < 16) -> replicated
    "ffn":     ("model",),
    "experts": ("model",),
    "embed":   ("data",),       # d_model dim of PARAMS: FSDP-style 2D
                                # sharding (model x data) so 30-50B param
                                # + optimizer states fit 16 GB/chip; on
                                # activations the batch dim claims "data"
                                # first, so h stays batch-sharded
                                # (TrainEngine overrides it: a cost model
                                # keeps its params whole along "data")
    "seq":     (),              # train/prefill seq: context-parallel override
    "cache_seq": ("model",),    # decode KV-cache sequence dim
    "qseq":    ("model",),      # query-seq context parallelism: picks up the
                                # model axis when head sharding can't (the
                                # attention layer gates this on divisibility)
    "conv_seq": (),
    "stack":   (),              # scanned-layer leading dim: never sharded
}


class ShardingRules:
    """Resolves logical axis names to PartitionSpecs on a concrete mesh."""

    def __init__(self, mesh: Mesh,
                 overrides: Optional[Dict[str, AxisRule]] = None):
        self.mesh = mesh
        self.rules = dict(DEFAULT_RULES)
        if overrides:
            for k, v in overrides.items():
                if v is None:
                    self.rules[k] = ()
                elif isinstance(v, str):
                    self.rules[k] = (v,)
                else:
                    self.rules[k] = tuple(v)
        self.axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))

    def _axes_for(self, logical: Optional[str],
                  dim: int) -> Optional[Tuple[str, ...]]:
        if logical is None:
            return None
        axes = [a for a in self.rules.get(logical, ()) if a in self.axis_sizes]
        kept = []
        remaining = dim
        for a in axes:
            n = self.axis_sizes[a]
            if remaining % n == 0 and n > 1:
                kept.append(a)
                remaining //= n
        if not kept:
            return None
        return tuple(kept)

    def spec(self, logical_axes: Sequence[Optional[str]],
             shape: Sequence[int]) -> P:
        assert len(logical_axes) == len(shape), (logical_axes, shape)
        used: set = set()
        parts = []
        for name, dim in zip(logical_axes, shape):
            axes = self._axes_for(name, dim)
            if axes is None:
                parts.append(None)
                continue
            axes = tuple(a for a in axes if a not in used)
            if not axes:
                parts.append(None)
                continue
            used.update(axes)
            parts.append(axes if len(axes) > 1 else axes[0])
        return P(*parts)

    def sharding(self, logical_axes: Sequence[Optional[str]],
                 shape: Sequence[int]) -> NamedSharding:
        return NamedSharding(self.mesh, self.spec(logical_axes, shape))

    def constrain(self, x, *logical_axes):
        """with_sharding_constraint using logical axes for x's shape."""
        return jax.lax.with_sharding_constraint(
            x, self.sharding(logical_axes, x.shape))

    def divisible(self, dim: int, axis: str) -> bool:
        n = self.axis_sizes.get(axis, 1)
        return n > 1 and dim % n == 0


def tree_shardings(rules: ShardingRules, tree_axes, tree_shapes):
    """Map a pytree of logical-axis tuples + matching shapes to
    NamedShardings."""
    return jax.tree.map(
        lambda axes, shape: rules.sharding(axes, shape),
        tree_axes, tree_shapes,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x))
