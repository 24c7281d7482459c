import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The two lines above MUST stay the first statements in this module — jax
locks the device count at first init, and the production meshes need 512
placeholder host devices. Nothing else in the repo sets this flag (tests
and benches see the real single device).

Usage::

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-0.6b \
        --shape train_4k [--multi-pod] [--all] [--out experiments/dryrun]

Per cell this builds the production mesh, resolves shardings for params /
optimizer state / batch / cache, lowers the appropriate step function with
jax.jit(..., in_shardings=..., out_shardings=...), compiles, and records
memory_analysis() + cost_analysis() + the collective-bytes breakdown that
the roofline report (launch/roofline.py) consumes.
"""
import argparse
import json
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs import ARCHS, SHAPES, get_arch, shape_eligible
from repro.launch import hlo_cost as HC
from repro.launch import roofline as RL
from repro.launch.mesh import make_production_mesh
from repro.models import model as MODEL
from repro.models import steps as STEPS
from repro.optim import adamw
from repro.runtime.sharding import ShardingRules

# decode cells whose bf16 KV cache exceeds per-chip HBM: serve with an
# int8-quantized cache (production KV-cache quantization).
INT8_KV_CELLS = {("qwen1.5-32b", "decode_32k")}


def _tree_shardings(rules: ShardingRules, axes_tree, abstract_tree):
    def is_axes(x):
        return isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x)
    return jax.tree.map(
        lambda axes, leaf: rules.sharding(axes, leaf.shape),
        axes_tree, abstract_tree, is_leaf=is_axes)


def _batch_shardings(rules: ShardingRules, specs: Dict[str, Any]):
    out = {}
    for k, v in specs.items():
        axes = ("batch",) + (None,) * (len(v.shape) - 1)
        out[k] = rules.sharding(axes, v.shape)
    return out


def _mem_summary(compiled) -> Dict[str, float]:
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return {}
    out = {}
    for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes", "alias_size_in_bytes",
                 "generated_code_size_in_bytes"):
        v = getattr(ma, attr, None)
        if v is not None:
            out[attr] = float(v)
    return out


def _cost_summary(compiled) -> Dict[str, float]:
    return {k: float(v) for k, v in compiled.cost_analysis().items()
            if isinstance(v, (int, float))}


def run_cell(arch_name: str, shape_name: str, *, multi_pod: bool = False,
             mesh=None, rules_overrides: Optional[Dict] = None,
             remat: bool = True, kv_dtype=None, grad_bf16: bool = False,
             pad_heads: bool = True,
             verbose: bool = True) -> Dict[str, Any]:
    """Lower+compile one (arch, shape, mesh) cell; return the record dict."""
    cfg = get_arch(arch_name)
    shape = SHAPES[shape_name]
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    record: Dict[str, Any] = {"arch": arch_name, "shape": shape_name,
                              "mesh": mesh_name}
    ok, reason = shape_eligible(cfg, shape)
    if not ok:
        record.update(status="skipped", reason=reason)
        return record

    t0 = time.perf_counter()
    mesh = mesh or make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    overrides = dict(rules_overrides or {})
    if shape.kind != "train" and "embed" not in overrides:
        # inference: no optimizer states to fit, so drop the FSDP (data-
        # axis) dimension of the 2D param sharding — weights stay TP-
        # sharded over model and replicated over data, killing the
        # per-layer weight gathers that dominate decode collectives
        overrides["embed"] = None
    rules = ShardingRules(mesh, overrides=overrides)
    rules.pad_attention_heads = pad_heads
    abs_params = STEPS.abstract_params(cfg)
    paxes = MODEL.param_axes(cfg)
    p_sh = _tree_shardings(rules, paxes, abs_params)
    specs = STEPS.input_specs(cfg, shape)
    b_sh = _batch_shardings(rules, specs)

    with mesh:
        if shape.kind == "train":
            opt_cfg = adamw.AdamWConfig()
            abs_opt = STEPS.abstract_opt_state(abs_params)
            o_sh = {"m": p_sh, "v": p_sh,
                    "count": jax.sharding.NamedSharding(
                        mesh, jax.sharding.PartitionSpec())}
            gt = None
            if grad_bf16:
                # bf16 gradients on the wire (the DP all-reduce payload
                # halves); optimizer math stays fp32
                def gt(g):
                    return jax.tree.map(
                        lambda x: x.astype(jnp.bfloat16), g)
            step = STEPS.make_train_step(cfg, opt_cfg, rules=rules,
                                         remat=remat, grad_transform=gt)
            jitted = jax.jit(step,
                             in_shardings=(p_sh, o_sh, b_sh),
                             out_shardings=(p_sh, o_sh, None),
                             donate_argnums=(0, 1))
            lowered = jitted.lower(abs_params, abs_opt, specs)
        elif shape.kind == "prefill":
            step = STEPS.make_prefill_step(cfg, rules=rules)
            jitted = jax.jit(step, in_shardings=(p_sh, b_sh))
            lowered = jitted.lower(abs_params, specs)
        else:  # decode
            kvd = kv_dtype
            if kvd is None:
                kvd = jnp.int8 if (arch_name, shape_name) in INT8_KV_CELLS \
                    else jnp.bfloat16
            abs_cache = STEPS.abstract_cache(cfg, shape.global_batch,
                                             shape.seq_len, kv_dtype=kvd)
            caxes = MODEL.cache_axes(cfg)
            c_sh = _tree_shardings(rules, caxes, abs_cache)
            step = STEPS.make_decode_step(cfg, rules=rules)
            jitted = jax.jit(
                step,
                in_shardings=(p_sh, c_sh, b_sh["tokens"], None),
                out_shardings=(b_sh["tokens"], c_sh),
                donate_argnums=(1,))
            lowered = jitted.lower(abs_params, abs_cache, specs["tokens"],
                                   jax.ShapeDtypeStruct((), jnp.int32))
        t_lower = time.perf_counter() - t0
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0 - t_lower

    mem = _mem_summary(compiled)
    cost = _cost_summary(compiled)
    # loop-aware HLO walk (XLA-CPU cost_analysis counts while bodies once;
    # see launch/hlo_cost.py) — this is the roofline source of truth.
    totals = HC.analyze_hlo(compiled.as_text())
    report = RL.RooflineReport(
        arch=arch_name, shape=shape_name, mesh=mesh_name, chips=chips,
        flops_per_chip=totals.flops,
        bytes_per_chip=totals.hbm_bytes,
        coll_bytes_per_chip=totals.coll_bytes,
        coll_breakdown=dict(totals.coll),
        peak_memory_per_chip=(mem.get("argument_size_in_bytes", 0.0)
                              + mem.get("temp_size_in_bytes", 0.0)
                              - mem.get("alias_size_in_bytes", 0.0)),
        model_flops=RL.model_flops_for(cfg, shape),
    )
    record.update(status="ok", chips=chips,
                  lower_s=round(t_lower, 1), compile_s=round(t_compile, 1),
                  memory=mem, cost=cost, roofline=report.to_dict())
    if verbose:
        print(f"[{mesh_name}] {arch_name} x {shape_name}: OK "
              f"({t_lower:.0f}s lower, {t_compile:.0f}s compile) "
              f"bottleneck={report.bottleneck} "
              f"t=({report.t_compute*1e3:.2f},{report.t_memory*1e3:.2f},"
              f"{report.t_collective*1e3:.2f})ms "
              f"roofline={report.roofline_fraction:.2%}")
        sizes = {k: f'{v/2**30:.2f}GiB'
                 for k, v in mem.items() if 'size' in k}
        print(f"  memory_analysis: {sizes}")
    return record


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    cells = []
    archs = list(ARCHS) if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    for mp in meshes:
        for a in archs:
            for s in shapes:
                cells.append((a, s, mp))

    os.makedirs(args.out, exist_ok=True)
    n_ok = n_skip = n_fail = 0
    for arch, shp, mp in cells:
        mesh_name = "pod2x16x16" if mp else "pod16x16"
        path = os.path.join(args.out,
                            f"{mesh_name}__{arch}__{shp}.json")
        if os.path.exists(path):
            with open(path) as f:
                rec = json.load(f)
            if rec.get("status") in ("ok", "skipped"):
                print(f"[cached] {mesh_name} {arch} x {shp}: "
                      f"{rec['status']}")
                n_ok += rec["status"] == "ok"
                n_skip += rec["status"] == "skipped"
                continue
        try:
            rec = run_cell(arch, shp, multi_pod=mp,
                           remat=not args.no_remat)
            n_ok += rec["status"] == "ok"
            n_skip += rec["status"] == "skipped"
            if rec["status"] == "skipped":
                print(f"[{mesh_name}] {arch} x {shp}: SKIPPED "
                      f"({rec['reason']})")
        except Exception as e:
            n_fail += 1
            rec = {"arch": arch, "shape": shp, "mesh": mesh_name,
                   "status": "failed", "error": f"{type(e).__name__}: {e}",
                   "traceback": traceback.format_exc()[-2000:]}
            print(f"[{mesh_name}] {arch} x {shp}: FAILED {type(e).__name__}: "
                  f"{str(e)[:200]}")
        with open(path, "w") as f:
            json.dump(rec, f, indent=1)
    print(f"\ndry-run summary: {n_ok} ok, {n_skip} skipped, {n_fail} failed")
    return 0 if n_fail == 0 else 1


if __name__ == "__main__":
    raise SystemExit(main())
