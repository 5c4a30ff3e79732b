import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("REPRO_DRYRUN_XLA_FLAGS")
    or "--xla_force_host_platform_device_count=512"
)

"""Dry-run of the paper's system at production scale: the two-pass DTW
cascade over a 1M-series database sharded across the full pod.

Lowers + compiles the shard_map'd search (repro.core.distributed) for
the 16x16 / 2x16x16 meshes with ShapeDtypeStruct inputs and extracts the
same artifact fields as the LM cells (collective bytes, memory).  The
cascade's compute is VPU (elementwise) work, not MXU dots, so the
compute term is derived analytically (see benchmarks/roofline notes).

  python -m repro.launch.search_dryrun --mesh pod
"""

import argparse  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.core.distributed import _sharded_search_fn  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.hlo_analysis import analyze_hlo  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402

ARTIFACT_DIR = os.path.join(os.path.dirname(__file__), "../../../benchmarks/artifacts")


def run_search_cell(
    mesh_kind: str = "pod",
    n_db: int = 1_048_576,
    length: int = 1000,
    w: int = 100,
    block: int = 32,
    sync_every: int = 4,
    k: int = 1,
    out_dir: str = ARTIFACT_DIR,
):
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multipod"))
    axis_names = tuple(mesh.axis_names)
    shards = 1
    for s in mesh.devices.shape:
        shards *= s
    assert n_db % (shards * block) == 0, (n_db, shards, block)

    fn = _sharded_search_fn(
        mesh, axis_names, w, 1, k, block, sync_every, "lb_improved"
    )
    q = jax.ShapeDtypeStruct((length,), jnp.float32)
    db = jax.ShapeDtypeStruct((n_db, length), jnp.float32)
    t0 = time.perf_counter()
    lowered = fn.lower(q, db)
    compiled = lowered.compile()
    dt = time.perf_counter() - t0

    try:
        mem = compiled.memory_analysis()
        memory = {
            kk: int(getattr(mem, kk))
            for kk in (
                "argument_size_in_bytes",
                "output_size_in_bytes",
                "temp_size_in_bytes",
            )
            if hasattr(mem, kk)
        }
    except Exception as e:
        memory = {"error": str(e)}
    coll = analyze_hlo(compiled.as_text())

    # analytic VPU op count per device (worst case, zero pruning):
    # lb1 ~6n/series + pass2 ~12n + DTW DP ~6 ops/cell * n*(2w+1)
    per_dev = n_db // shards
    ops_lb = per_dev * (6 * length + 12 * length)
    ops_dtw = per_dev * length * (2 * w + 1) * 6
    result = {
        "arch": "dtw-search-1m",
        "shape": f"db{n_db}x{length}_w{w}_b{block}_s{sync_every}",
        "mesh": mesh_kind,
        "ok": True,
        "skipped": False,
        "n_params": 0,
        "compile_sec": dt,
        "flops": float(ops_lb + ops_dtw),  # VPU ops, worst case (no pruning)
        "bytes_accessed": float(coll["hbm_bytes"]),
        "collective_bytes": coll["collective_bytes"],
        "collective_by_kind": coll["by_kind"],
        "memory": memory,
        "policy": {
            "block": block,
            "sync_every": sync_every,
            "note": "flops=worst-case VPU ops (pruning is data-dependent)",
        },
    }
    os.makedirs(out_dir, exist_ok=True)
    with open(
        os.path.join(out_dir, f"dtw-search-1m__scan__{mesh_kind}.json"), "w"
    ) as f:
        json.dump(result, f, indent=1)
    print(
        f"[dtw-search x {mesh_kind}] compiled in {dt:.1f}s  memory={memory}\n"
        f"  worst-case VPU ops/device={result['flops']:.3e}  "
        f"collectives={coll['collective_bytes']:.3e} {coll['by_kind']}"
    )
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", choices=("pod", "multipod", "both"), default="both")
    ap.add_argument("--block", type=int, default=32)
    ap.add_argument("--sync-every", type=int, default=4)
    args = ap.parse_args()
    enable_compile_cache()
    meshes = ("pod", "multipod") if args.mesh == "both" else (args.mesh,)
    for mk in meshes:
        run_search_cell(mk, block=args.block, sync_every=args.sync_every)


if __name__ == "__main__":
    main()
