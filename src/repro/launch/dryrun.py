import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("REPRO_DRYRUN_XLA_FLAGS")
    or "--xla_force_host_platform_device_count=512"
)

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this builds the production mesh (16x16 single-pod or
2x16x16 multi-pod), constructs the model at FULL size (params as
ShapeDtypeStructs — nothing is allocated), applies the per-cell
parallelism policy, jits the appropriate step function with explicit
NamedShardings, and runs ``.lower().compile()``.  Success proves the
sharding configuration is coherent; the compiled artifact yields

  * ``memory_analysis()``  — per-device bytes (the "fits" proof),
  * ``cost_analysis()``    — HLO FLOPs / bytes for §Roofline,
  * optimized HLO text     — collective traffic via launch.hlo_analysis.

Artifacts land in benchmarks/artifacts/<cell>.json; benchmarks/roofline.py
turns them into the EXPERIMENTS.md tables.

Usage:
  python -m repro.launch.dryrun --arch granite-3-2b --shape train_4k --mesh pod
  python -m repro.launch.dryrun --all --mesh both
"""

import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ParallelConfig, ShapeConfig
from repro.configs.registry import (
    ARCH_IDS,
    all_cells,
    cell_is_runnable,
    get_config,
    get_shape,
)
from repro.distributed.sharding import ShardingRules, fit_tree, make_rules, use_rules
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.hlo_analysis import analyze_hlo
from repro.launch.mesh import make_production_mesh, mesh_axis_sizes
from repro.launch.policy import apply_overrides, optimizer_for_cell, parallel_for_cell
from repro.models.common import _nest
from repro.models.model_zoo import Model, batch_specs, build_model
from repro.optim import OptimizerConfig, optimizer_init
from repro.models.lm_serve import make_serve_step
from repro.train.train_step import make_train_step

ARTIFACT_DIR = os.path.join(os.path.dirname(__file__), "../../../benchmarks/artifacts")


def rules_for(model: Model, mesh, parallel: ParallelConfig) -> ShardingRules:
    cfg = model.cfg
    n_kv = cfg.n_kv_heads
    if cfg.family == "hybrid":
        n_kv = cfg.hybrid.shared_n_kv
    return make_rules(
        mesh,
        n_kv_heads=n_kv,
        n_heads=cfg.n_heads,
        n_experts=cfg.moe.n_experts if cfg.moe else 0,
        seq_shard=parallel.seq_shard_activations,
        shard_kv_cache_seq=parallel.shard_kv_cache_seq,
        fsdp=parallel.fsdp,
        tensor_parallel=parallel.tensor_parallel,
    )


def param_shardings(model: Model, rules: ShardingRules):
    return jax.tree.map(
        lambda axes: rules.sharding(axes),
        model.param_axes,
        is_leaf=lambda x: isinstance(x, tuple),
    )


def opt_state_shardings(
    opt_cfg: OptimizerConfig, model: Model, rules: ShardingRules
):
    if opt_cfg.kind == "adamw":
        ps = param_shardings(model, rules)
        return {"m": ps, "v": ps}
    flat = {}
    for path, spec in model.specs.items():
        axes = spec.axes
        if len(spec.shape) >= 2 and min(spec.shape[-2:]) >= opt_cfg.min_dim_size_to_factor:
            flat[path] = {
                "vr": rules.sharding(axes[:-1]),
                "vc": rules.sharding(axes[:-2] + axes[-1:]),
                "m": rules.sharding(axes),
            }
        else:
            flat[path] = {"v": rules.sharding(axes), "m": rules.sharding(axes)}
    return _nest(flat)


def batch_shardings(model: Model, shape: ShapeConfig, rules: ShardingRules):
    def act(*axes):
        return rules.sharding(axes)

    if shape.kind == "train":
        sh = {
            "tokens": act("act_batch", "act_none"),
            "labels": act("act_batch", "act_none"),
        }
        if model.cfg.family == "vlm":
            sh["vision_embeds"] = act("act_batch", "act_none", "act_embed")
        if model.cfg.family == "audio":
            sh["frames"] = act("act_batch", "act_none", "act_embed")
        return sh
    if shape.kind == "prefill":
        sh = {"tokens": act("act_batch", "act_none")}
        if model.cfg.family == "vlm":
            sh["vision_embeds"] = act("act_batch", "act_none", "act_embed")
        if model.cfg.family == "audio":
            sh["frames"] = act("act_batch", "act_none", "act_embed")
        return sh
    cache_sh = jax.tree.map(
        lambda axes: rules.sharding(axes),
        model.cache_axes(),
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(a, (str, type(None))) for a in x
        ),
    )
    return {
        "tokens": act("act_batch", "act_none"),
        "pos": NamedSharding(rules.mesh, P()),
        "cache": cache_sh,
    }


@dataclasses.dataclass
class CellResult:
    arch: str
    shape: str
    mesh: str
    ok: bool
    skipped: bool = False
    reason: str = ""
    n_params: int = 0
    compile_sec: float = 0.0
    flops: float = 0.0
    bytes_accessed: float = 0.0
    collective_bytes: int = 0
    collective_by_kind: dict | None = None
    memory: dict | None = None
    policy: dict | None = None
    error: str = ""


def run_cell(
    arch: str,
    shape_name: str,
    mesh_kind: str,
    overrides: dict | None = None,
    save_hlo: bool = False,
) -> CellResult:
    cfg = get_config(arch)
    shape = get_shape(shape_name)
    ok, why = cell_is_runnable(cfg, shape)
    if not ok:
        return CellResult(arch, shape_name, mesh_kind, ok=True, skipped=True, reason=why)

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multipod"))
    sizes = mesh_axis_sizes(mesh)
    data_shards = sizes.get("data", 1) * sizes.get("pod", 1)

    probe = build_model(cfg)  # for param count only (specs are cheap)
    parallel = parallel_for_cell(cfg, shape, probe.n_params, data_shards)
    if overrides:
        parallel = apply_overrides(parallel, overrides)
    model = build_model(cfg, parallel)
    rules = rules_for(model, mesh, parallel)

    pdtype = jnp.dtype(parallel.param_dtype)
    params_abs = model.abstract_params(pdtype)
    p_shard = fit_tree(param_shardings(model, rules), params_abs)
    b_specs = batch_specs(model, shape)
    b_shard = fit_tree(batch_shardings(model, shape, rules), b_specs)

    t0 = time.perf_counter()
    with use_rules(rules):
        if shape.kind == "train":
            opt_cfg = optimizer_for_cell(cfg, parallel, probe.n_params)
            opt_abs = jax.eval_shape(
                lambda p: optimizer_init(opt_cfg, p), params_abs
            )
            o_shard = fit_tree(opt_state_shardings(opt_cfg, model, rules), opt_abs)
            step_fn = make_train_step(model, opt_cfg, parallel)
            jitted = jax.jit(
                step_fn,
                in_shardings=(p_shard, o_shard, b_shard, NamedSharding(mesh, P())),
                out_shardings=(p_shard, o_shard, None),
                donate_argnums=(0, 1),
            )
            lowered = jitted.lower(
                params_abs, opt_abs, b_specs, jax.ShapeDtypeStruct((), jnp.int32)
            )
        elif shape.kind == "prefill":
            def prefill(params, batch):
                return model.prefill_step(params, batch)

            jitted = jax.jit(prefill, in_shardings=(p_shard, b_shard))
            lowered = jitted.lower(params_abs, b_specs)
        else:  # decode
            serve = make_serve_step(model)
            cache_sh = b_shard["cache"]
            jitted = jax.jit(
                serve,
                in_shardings=(
                    p_shard,
                    cache_sh,
                    b_shard["tokens"],
                    b_shard["pos"],
                ),
                out_shardings=(b_shard["tokens"], cache_sh),
                donate_argnums=(1,),
            )
            lowered = jitted.lower(
                params_abs, b_specs["cache"], b_specs["tokens"], b_specs["pos"]
            )
        compiled = lowered.compile()
    compile_sec = time.perf_counter() - t0

    cost = compiled.cost_analysis() or {}
    if isinstance(cost, (list, tuple)):  # older jax wraps the dict in a list
        cost = cost[0] if cost else {}
    try:
        mem = compiled.memory_analysis()
        memory = {
            k: int(getattr(mem, k))
            for k in (
                "argument_size_in_bytes",
                "output_size_in_bytes",
                "temp_size_in_bytes",
                "generated_code_size_in_bytes",
            )
            if hasattr(mem, k)
        } if mem is not None else None
    except Exception as e:  # CPU backend may not implement it
        memory = {"error": str(e)}

    hlo = compiled.as_text()
    coll = analyze_hlo(hlo)
    if save_hlo:
        os.makedirs(ARTIFACT_DIR, exist_ok=True)
        with open(
            os.path.join(ARTIFACT_DIR, f"{arch}__{shape_name}__{mesh_kind}.hlo"), "w"
        ) as f:
            f.write(hlo)

    print(f"[{arch} x {shape_name} x {mesh_kind}] compiled in {compile_sec:.1f}s")
    print(f"  memory_analysis: {memory}")
    print(
        f"  cost_analysis(unweighted): flops={cost.get('flops', 0):.3e} "
        f"bytes={cost.get('bytes accessed', 0):.3e}"
    )
    print(
        f"  hlo walk (loop-weighted, per device): dot_flops={coll['dot_flops']:.3e} "
        f"hbm_bytes~={coll['hbm_bytes']:.3e}"
    )
    print(
        f"  collectives: total={coll['collective_bytes']:.3e} by_kind="
        f"{ {k: f'{v:.2e}' for k, v in coll['by_kind'].items()} } "
        f"warnings={len(coll['warnings'])}"
    )

    return CellResult(
        arch=arch,
        shape=shape_name,
        mesh=mesh_kind,
        ok=True,
        n_params=probe.n_params,
        compile_sec=compile_sec,
        flops=float(coll["dot_flops"]),
        bytes_accessed=float(coll["hbm_bytes"]),
        collective_bytes=coll["collective_bytes"],
        collective_by_kind=coll["by_kind"],
        memory=memory,
        policy=dataclasses.asdict(parallel),
    )


def save_result(res: CellResult, out_dir: str):
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"{res.arch}__{res.shape}__{res.mesh}.json")
    with open(path, "w") as f:
        json.dump(dataclasses.asdict(res), f, indent=1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--mesh", choices=("pod", "multipod", "both"), default="pod")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=ARTIFACT_DIR)
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument(
        "--override", action="append", default=[], help="key=value ParallelConfig override"
    )
    args = ap.parse_args()
    enable_compile_cache()

    overrides = {}
    for item in args.override:
        k, v = item.split("=", 1)
        overrides[k] = int(v) if v.lstrip("-").isdigit() else (
            v if v in ("none", "full", "dots", "float32", "bfloat16", "adamw", "adafactor")
            else v == "true"
        )

    meshes = ("pod", "multipod") if args.mesh == "both" else (args.mesh,)
    cells = (
        [(a, s) for a, s, _, _ in all_cells(include_skipped=True)]
        if args.all
        else [(args.arch, args.shape)]
    )
    failures = []
    for arch, shape_name in cells:
        for mesh_kind in meshes:
            try:
                res = run_cell(arch, shape_name, mesh_kind, overrides, args.save_hlo)
            except Exception as e:
                traceback.print_exc()
                res = CellResult(
                    arch, shape_name, mesh_kind, ok=False, error=f"{type(e).__name__}: {e}"
                )
                failures.append((arch, shape_name, mesh_kind))
            save_result(res, args.out)
    if failures:
        print(f"FAILED cells: {failures}")
        raise SystemExit(1)
    print("dry-run: all requested cells compiled")


if __name__ == "__main__":
    main()
