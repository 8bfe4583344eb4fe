import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

This is the scale proof without hardware: 512 placeholder host devices
stand in for two 256-chip pods; ``jax.jit(...).lower(*ShapeDtypeStructs)``
+ ``.compile()`` must succeed for every cell, and the compiled artifact
yields the roofline inputs (cost_analysis FLOPs/bytes, memory_analysis,
and collective bytes parsed from the partitioned HLO).

Usage:
    python -m repro.launch.dryrun --arch qwen3-1.7b --shape train_4k
    python -m repro.launch.dryrun --arch all --shape all --mesh both
    ... [--hp 8 --cp 2 --inner 1 --placement context_first]

One cell per process is recommended for the full sweep (see
scripts in EXPERIMENTS.md §Dry-run) — device count is locked at first jax
use, and cells are independent compiles.
"""
import argparse           # noqa: E402
import functools          # noqa: E402
import json               # noqa: E402
import time               # noqa: E402

import jax                # noqa: E402
import jax.numpy as jnp   # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.analysis.hlo import parse_collective_bytes       # noqa: E402
from repro.analysis.roofline import (count_params,          # noqa: E402
                                     model_flops)
from repro.configs import get_config, get_parallel, all_arch_names  # noqa
from repro.configs.common import SHAPES, applicable_shapes  # noqa: E402
from repro.core.plan import ExecutionPlan                   # noqa: E402
from repro.core.topology import ParallelConfig              # noqa: E402
from repro.launch import args as launch_args                # noqa: E402
from repro.launch.mesh import production_plan               # noqa: E402
from repro.models.decode import (cache_shardings,           # noqa: E402
                                 decode_step, init_caches, prefill)
from repro.models.model import init_params, ModelConfig     # noqa: E402
from repro.train.optimizer import init_opt_state            # noqa: E402
from repro.train.train_step import make_train_step          # noqa: E402


def input_specs(plan: ExecutionPlan, shape_name: str):
    """ShapeDtypeStruct stand-ins + the plan's NamedShardings for every
    step input.

    Weak-type-correct, shardable, no device allocation (the shannon/kernels
    pattern).  Returns (structs, shardings) dictionaries keyed like the
    step function's batch argument.
    """
    cfg, shape = plan.cfg, SHAPES[shape_name]
    b, s = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    shards = plan.batch_shardings(shape.kind)
    structs = {}

    if shape.kind == "train":
        for k in ("tokens", "labels", "positions"):
            structs[k] = jax.ShapeDtypeStruct((b, s), i32)
    elif shape.kind == "prefill":
        structs["tokens"] = jax.ShapeDtypeStruct((b, s), i32)
    else:  # decode
        structs["tokens"] = jax.ShapeDtypeStruct((b, 1), i32)
    if shape.kind != "decode" and cfg.family == "encdec":
        structs["frames"] = jax.ShapeDtypeStruct(
            (b, cfg.enc_frames, cfg.d_model), cfg.compute_dtype)
    return structs, {k: shards[k] for k in structs}


def _mem_summary(compiled):
    try:
        ma = compiled.memory_analysis()
    except Exception as e:                              # pragma: no cover
        return {"error": str(e)}
    out = {}
    for k in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "generated_code_size_in_bytes",
              "alias_size_in_bytes", "peak_memory_in_bytes"):
        v = getattr(ma, k, None)
        if v is not None:
            out[k] = int(v)
    return out


def _cost_summary(compiled):
    try:
        ca = compiled.cost_analysis()
    except Exception as e:                              # pragma: no cover
        return {"error": str(e), "flops": 0.0}
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    return {"flops": float(ca.get("flops", 0.0)),
            "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
            "transcendentals": float(ca.get("transcendentals", 0.0))}


def _layer_group_period(cfg: ModelConfig) -> int:
    if cfg.family == "hybrid":
        return cfg.attn_every
    return cfg.period


def _with_groups(cfg: ModelConfig, groups: int) -> ModelConfig:
    import dataclasses
    period = _layer_group_period(cfg)
    kw = {"num_layers": groups * period}
    if cfg.family == "encdec":
        kw["encoder_layers"] = groups
        kw["num_layers"] = groups
    return dataclasses.replace(cfg, **kw)


def _compile_cell(plan, shape, *, donate=True, param_sharding="zero"):
    """lower+compile one variant; returns (compiled, t_lower, t_compile)."""
    cfg, rt, mesh = plan.cfg, plan.rt, plan.mesh
    structs, shards = input_specs(plan, shape.name)
    key = jax.random.PRNGKey(0)
    p_struct = jax.eval_shape(lambda: init_params(cfg, key))
    p_sh = plan.serve_shardings(p_struct) if param_sharding == "tp" \
        else plan.param_shardings(p_struct)
    t0 = time.time()
    with mesh:
        if shape.kind == "train":
            o_struct = jax.eval_shape(init_opt_state, p_struct)
            o_sh = plan.opt_shardings(p_sh)
            fn = make_train_step(plan)
            jitted = jax.jit(fn, in_shardings=(p_sh, o_sh, shards),
                             out_shardings=(p_sh, o_sh, None),
                             donate_argnums=(0, 1) if donate else ())
            lowered = jitted.lower(p_struct, o_struct, structs)
        elif shape.kind == "prefill":
            fn = lambda p, b: prefill(p, b, rt, cfg)   # noqa: E731
            jitted = jax.jit(fn, in_shardings=(p_sh, shards))
            lowered = jitted.lower(p_struct, structs)
        else:
            c_struct = jax.eval_shape(functools.partial(
                init_caches, cfg, shape.global_batch, shape.seq_len))
            c_sh = cache_shardings(cfg, c_struct, mesh, rt.batch_axes)
            pos_struct = jax.ShapeDtypeStruct((), jnp.int32)
            fn = lambda p, c, t, pos: decode_step(    # noqa: E731
                p, c, t, pos, rt, cfg)
            jitted = jax.jit(
                fn,
                in_shardings=(p_sh, c_sh, shards["tokens"],
                              NamedSharding(mesh, P())),
                donate_argnums=(1,) if donate else ())
            lowered = jitted.lower(p_struct, c_struct, structs["tokens"],
                                   pos_struct)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0
    return compiled, t_lower, t_compile


def _extrapolate(v1: float, v2: float, g_full: float) -> float:
    """Affine in group count: v(g) = a + b·g fitted at g=1,2.

    The slope is clamped at >= 0: compiler noise between the two variants
    (different fusion/CSE choices) must not extrapolate negative.
    """
    b = max(v2 - v1, 0.0)
    a = v1 - b
    return max(a + b * g_full, v1)


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             pc: ParallelConfig | None = None, impl: str = "ref",
             remat: str | None = None, out_dir: str | None = None,
             hlo_out: str | None = None, tag_extra: str = "",
             param_sharding: str = "zero",
             plan_only: bool = False, tune_table: bool = False) -> dict:
    """One dry-run cell.

    The full-size model compiles with scanned layers (the scale/memory
    proof).  XLA cost analysis counts a while body once, so FLOPs and
    collective bytes are measured on *unrolled* 1-group and 2-group
    variants and extrapolated affinely in depth — exact for homogeneous
    stacks (zamba2's 3 tail layers ≈ +0.5 group, <1% error).
    """
    import dataclasses
    shape = SHAPES[shape_name]
    if pc is None:
        pc = get_parallel(arch, shape_name, multi_pod)
    plan = production_plan(get_config(arch), pc, multi_pod=multi_pod,
                           impl=impl, remat=remat,
                           seq_len=shape.seq_len,
                           global_batch=shape.global_batch)
    cfg, mesh = plan.cfg, plan.mesh
    chips = mesh.size
    if plan_only:
        desc = plan.describe()
        print(desc)
        rec = {"arch": arch, "shape": shape_name, "plan_only": True,
               "describe": desc}
        if tune_table:
            # PlanTuner's top-5 for this cell's frame (dp pinned to the
            # production layout; the model-axis split, placement and the
            # execution knobs are up for grabs) — the placement
            # trade-offs, inspectable without compiling anything.
            from repro.tune import tune
            result = tune(cfg, num_devices=mesh.size,
                          seq_len=shape.seq_len,
                          global_batch=shape.global_batch,
                          pods=pc.pods, dp=pc.dp,
                          memory_budget_gb=16.0, arch=arch)
            table = result.table(top=5)
            print(table)
            rec["tune_table"] = table
            if result.ranked:
                rec["tuned"] = result.tuned_plan().to_json()
        return rec

    # 1) full-size scanned compile — the dry-run pass/fail + memory truth
    compiled, t_lower, t_compile = _compile_cell(
        plan, shape, param_sharding=param_sharding)
    mem = _mem_summary(compiled)
    hlo = compiled.as_text()
    if hlo_out:
        with open(hlo_out, "w") as f:
            f.write(hlo)

    # 2) unrolled 1-group / 2-group compiles — per-layer cost slopes
    period = _layer_group_period(cfg)
    g_full = cfg.num_layers / period if cfg.family != "encdec"         else cfg.num_layers
    cost, coll = {}, {}
    for g in (1, 2):
        cfg_g = dataclasses.replace(_with_groups(cfg, g),
                                    unroll_loops=True)
        comp_g, _, _ = _compile_cell(dataclasses.replace(plan, cfg=cfg_g),
                                     shape, donate=False,
                                     param_sharding=param_sharding)
        cost[g] = _cost_summary(comp_g)
        coll[g] = parse_collective_bytes(comp_g.as_text())
    cost_x = {k: _extrapolate(cost[1].get(k, 0.0), cost[2].get(k, 0.0),
                              g_full)
              for k in ("flops", "bytes_accessed", "transcendentals")}
    coll_total = _extrapolate(coll[1]["total"], coll[2]["total"], g_full)
    wire_total = _extrapolate(coll[1].get("wire_total", 0),
                              coll[2].get("wire_total", 0), g_full)
    coll_by_op = {k: int(_extrapolate(coll[1]["by_op"].get(k, 0),
                                      coll[2]["by_op"].get(k, 0), g_full))
                  for k in set(coll[1]["by_op"]) | set(coll[2]["by_op"])}
    wire_by_op = {k: int(_extrapolate(coll[1].get("wire_by_op", {}).get(k, 0),
                                      coll[2].get("wire_by_op", {}).get(k, 0),
                                      g_full))
                  for k in set(coll[1].get("wire_by_op", {}))
                  | set(coll[2].get("wire_by_op", {}))}

    n_total, n_active = count_params(cfg)
    rec = {
        "arch": arch, "shape": shape_name,
        "mesh": "2pod" if multi_pod else "1pod", "chips": chips,
        "pc": f"hp{pc.hp}/cp{pc.cp_outer}x{pc.cp_inner}/"
              f"{'hf' if pc.placement == 'head_first' else 'cf'}",
        "kind": shape.kind, "impl": impl, "remat": cfg.remat,
        "param_sharding": param_sharding,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "cost": cost_x,
        "cost_raw": {"g1": cost[1], "g2": cost[2], "g_full": g_full},
        "memory": mem,
        "collectives": {"total": int(coll_total),
                        "wire_total": int(wire_total),
                        "by_op": coll_by_op, "wire_by_op": wire_by_op,
                        "counts_g1": coll[1]["counts"],
                        "raw": {"g1": coll[1], "g2": coll[2]}},
        "n_params": n_total, "n_active": n_active,
        "model_flops": model_flops(cfg, shape.kind, shape.seq_len,
                                   shape.global_batch, n_active),
    }
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        tag = (f"{arch}_{shape_name}_{rec['mesh']}_"
               f"{rec['pc'].replace('/', '-')}{tag_extra}")
        with open(os.path.join(out_dir, tag + ".json"), "w") as f:
            json.dump(rec, f, indent=1)
    return rec


def main():
    ap = argparse.ArgumentParser()
    launch_args.add_arch(ap, arch_help="architecture id or 'all'")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="1pod",
                    choices=["1pod", "2pod", "both"])
    ap.add_argument("--hp", type=int)
    ap.add_argument("--cp-outer", type=int)
    ap.add_argument("--inner", type=int)
    ap.add_argument("--placement", choices=["head_first", "context_first"])
    ap.add_argument("--impl", default="ref")
    ap.add_argument("--remat", default=None)
    ap.add_argument("--param-sharding", default="zero",
                    choices=["zero", "tp"])
    ap.add_argument("--tag", default="")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--hlo-out", default=None)
    ap.add_argument("--plan", action="store_true",
                    help="print ExecutionPlan.describe() per cell and "
                         "skip the compiles (fast plan regression smoke)")
    ap.add_argument("--tune", action="store_true",
                    help="with --plan: also print the PlanTuner's top-5 "
                         "candidate table per cell (enumerate+score "
                         "only, nothing runs)")
    args = ap.parse_args()

    archs = all_arch_names() if args.arch == "all" else [args.arch]
    meshes = ["1pod", "2pod"] if args.mesh == "both" else [args.mesh]
    for arch in archs:
        shapes = applicable_shapes(arch) if args.shape == "all" \
            else [args.shape]
        for shape in shapes:
            for mesh_kind in meshes:
                multi = mesh_kind == "2pod"
                pc = None
                if args.hp:
                    base = get_parallel(arch, shape, multi)
                    inner = args.inner or min(args.cp_outer or base.cp, 4)
                    cp = (args.cp_outer or (16 // args.hp) // inner) * inner
                    pc = ParallelConfig(
                        dp=16, hp=args.hp, cp_outer=cp // inner,
                        cp_inner=inner, pods=2 if multi else 1,
                        placement=args.placement or base.placement)
                rec = run_cell(arch, shape, multi_pod=multi, pc=pc,
                               impl=args.impl, remat=args.remat,
                               out_dir=args.out, hlo_out=args.hlo_out,
                               param_sharding=args.param_sharding,
                               tag_extra=args.tag, plan_only=args.plan,
                               tune_table=args.plan and args.tune)
                if args.plan:
                    continue
                c = rec["cost"]
                print(f"[dryrun] {arch} {shape} {rec['mesh']} {rec['pc']}: "
                      f"flops/dev={c['flops']:.3e} "
                      f"coll/dev={rec['collectives']['total']:.3e}B "
                      f"compile={rec['compile_s']}s")


if __name__ == "__main__":
    main()
