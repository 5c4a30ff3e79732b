#!/usr/bin/env python3
"""Smoke test: the DTW search service end to end on a TPU.

One chip (the default) runs the served path at the source paper's
series length, through the entry points a user calls, one line per
phase:

  device   jax.devices() must be TPUs; there is no CPU fallback
  build    Database.build over 262,144 random walks x n=1000, float32,
           SearchConfig(w=0, k=5): w resolves to n // 10 = 100, p = 1
  serve    a QueryEngine answers 32 requests of the mixed workload
           (repeats, near-duplicates, cold walks) from 4 client threads;
           every answer is bit-identical to a direct db.search
  exact    4 of those queries: the planner's host driver (their rows of
           the direct search), driver="scan" and method="full" (no
           pruning) agree bit for bit, and the top-k distances match the
           float64 O(n^2) oracle within rtol 2e-4
  kernels  every Pallas family the TPU compiler accepts runs with
           interpret=False and matches its ref.py oracle

``--chips 4`` runs only the sharded sweep and what it is compared with:
4 x 262,144 rows sharded over a 4-device mesh, against the one-chip
scan driver on the same rows (bit-identical), with every device's peak
memory (no device may hold the whole database).  That session uses
block=256 (results never depend on it): the one-chip scan then runs
4,096 sequential block steps instead of 32,768.

Timings are smoke figures, not benchmark results.  The last line of
stdout is one JSON object naming the device.  Without a TPU the script
exits non-zero and prints no result.  ``--tiny`` shrinks every size so
the phases can be rehearsed on the CPU; it still exits non-zero there.

Usage:
  python chip_smoke.py
  python chip_smoke.py --chips 4
  JAX_PLATFORMS=cpu python chip_smoke.py --tiny
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
      python chip_smoke.py --tiny --chips 4
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

#: (rows per chip, series length, kernel candidates, kernel queries)
FULL = dict(rows=262_144, length=1000, kernel_b=256, kernel_q=8)
TINY = dict(rows=2048, length=128, kernel_b=32, kernel_q=4)
K = 5
REQUESTS = 32
CLIENTS = 4
#: the engine batches every request into one (32, n) sweep: on the
#: chip a host-driver sweep of 262,144 rows costs minutes whatever its
#: batch width, so fewer, wider batches keep the smoke inside its limit
MAX_BATCH = 32
MAX_WAIT_MS = 250.0
SHARDED_BLOCK = 256
EXACT_QUERIES = 4
SHARDED_QUERIES = 8
DTW_RTOL = 2e-4


class SmokeFailure(AssertionError):
    """A phase established something other than what it checks."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def peak_bytes(device) -> int | None:
    """``peak_bytes_in_use`` where the backend reports it (TPU), else None."""
    stats = device.memory_stats()
    return None if stats is None else int(stats.get("peak_bytes_in_use", 0))


def same_answers(a, b) -> bool:
    return np.array_equal(a.distances, b.distances) and np.array_equal(
        a.indices, b.indices
    )


# ------------------------------------------------------------------ phases


def phase_device(args):
    import jax

    devs = jax.devices()
    dev = devs[0]
    line = (
        f"platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devs)} jax={jax.__version__}"
    )
    if args.chips > len(devs):
        raise SmokeFailure(f"{line}: --chips {args.chips} needs that many devices")
    if dev.platform != "tpu" and not args.tiny:
        raise SmokeFailure(f"{line}: no TPU, and this script never falls back")
    return line, {"platform": dev.platform, "kind": dev.device_kind,
                  "count": len(devs)}


def build_session(rows: int, length: int, seed: int, block: int = 32):
    from repro.api import Database, SearchConfig
    from repro.data.synthetic import random_walks

    rng = np.random.default_rng(seed)
    t0 = time.perf_counter()
    data = random_walks(rng, rows, length)
    t_gen = time.perf_counter() - t0
    t0 = time.perf_counter()
    db = Database.build(data, SearchConfig(w=0, k=K, block=block))
    t_build = time.perf_counter() - t0
    check(db.w == length // 10 and db.p == 1, f"w={db.w} p={db.p}")
    check(
        bool((db.upper >= db.data).all() and (db.lower <= db.data).all()),
        "envelopes do not bracket the rows",
    )
    line = (
        f"{rows} x {length} float32 ({data.nbytes / 2**30:.3f} GiB) "
        f"w={db.w} p={db.p} k={K} block={block}; generated in {t_gen:.2f}s, "
        f"Database.build {t_build:.2f}s"
    )
    return rng, data, db, line


def phase_build(args, sizes, state):
    import jax

    rng, data, db, line = build_session(sizes["rows"], sizes["length"], args.seed)
    state.update(rng=rng, data=data, db=db)
    return f"{line}; peak_bytes_in_use={peak_bytes(jax.devices()[0])}"


def phase_serve(args, sizes, state):
    from repro.launch.serve import mixed_workload, replay
    from repro.serve import QueryEngine

    db, data = state["db"], state["data"]
    workload = mixed_workload(state["rng"], data, REQUESTS)
    state["workload"] = workload
    driver = db.plan(MAX_BATCH).driver
    engine = QueryEngine(db, max_batch=MAX_BATCH, max_wait_ms=MAX_WAIT_MS)
    try:
        t0 = time.perf_counter()
        served = replay(engine, workload, CLIENTS)
        wall = time.perf_counter() - t0
        stats = engine.stats()
    finally:
        engine.close()
    check(len(served) == REQUESTS, f"{len(served)} of {REQUESTS} answered")
    direct = db.search(workload)
    state["direct"] = direct
    for qi, _, ans in served:
        check(
            np.array_equal(ans.distances, direct.distances[qi])
            and np.array_equal(ans.indices, direct.indices[qi]),
            f"request {qi}: engine answer differs from db.search",
        )
    lat_ms = np.sort([1e3 * dt for _, dt, _ in served])
    return (
        f"{REQUESTS} requests from {CLIENTS} clients, driver={driver}: "
        f"every answer bit-identical to db.search; "
        f"batches={stats.batches} coalesced={stats.coalesced} "
        f"cache_hits={stats.cache_hits}; smoke timing, not a metric: "
        f"{REQUESTS / wall:.3f} qps, p50={np.percentile(lat_ms, 50):.1f} ms "
        f"p99={np.percentile(lat_ms, 99):.1f} ms (compiles included)"
    )


def phase_exact(args, sizes, state):
    from repro.core.dtw import dtw_reference

    db = state["db"]
    qs = state["workload"][:EXACT_QUERIES]
    m = len(qs)
    check(db.plan(qs).driver == "host", "planner did not choose host")
    # the serve phase's direct search ran the host driver over the whole
    # workload, and a batch row is bit-identical to searching it alone
    direct = state["direct"]
    check(db.plan(len(direct)).driver == "host", "direct search not host")
    host_d, host_i = direct.distances[:m], direct.indices[:m]
    scan = db.search(qs, driver="scan")
    full = db.search(qs, method="full")
    for name, got in (("driver='scan'", scan), ("method='full'", full)):
        check(
            np.array_equal(got.distances, host_d)
            and np.array_equal(got.indices, host_i),
            f"host driver and {name} disagree",
        )
    check(
        full.stats.full_dtw == m * db.n_rows,
        f"method='full' ran {full.stats.full_dtw} DPs, not every pair",
    )
    prepared = db.prepare_queries(qs)
    worst = 0.0
    for i in range(m):
        check(bool(np.all(np.diff(host_d[i]) >= 0)), "top-k unsorted")
        for dist, idx in zip(host_d[i], host_i[i]):
            ref = dtw_reference(prepared[i], db.data[idx], db.w, p=1)
            worst = max(worst, abs(float(dist) - ref) / max(abs(ref), 1e-30))
    check(worst <= DTW_RTOL, f"max rel err {worst:.3e} vs dtw_reference")
    dps = sum(st.full_dtw for st in direct.per_query[:m])
    return (
        f"{m} queries: host == scan == method='full' bit for bit; "
        f"top-{K} vs float64 dtw_reference max rel err {worst:.3e} "
        f"(limit {DTW_RTOL}); the host driver ran {dps} DPs for "
        f"{m * db.n_rows} pairs"
    )


def _kernel_inputs(sizes, seed):
    import jax.numpy as jnp

    from repro.core.envelope import envelope_batch
    from repro.data.synthetic import random_walks

    rng = np.random.default_rng(seed + 1)
    n = sizes["length"]
    w = n // 10
    cands = jnp.asarray(random_walks(rng, sizes["kernel_b"], n))
    qs = jnp.asarray(random_walks(rng, sizes["kernel_q"], n))
    upper, lower = envelope_batch(qs, w)
    mask = jnp.asarray(rng.random((sizes["kernel_q"], sizes["kernel_b"])) > 0.25)
    return cands, qs, upper, lower, mask


def _check_lb_kim(inputs, interpret):
    from repro.kernels import lb_kim_qbatch_op, lb_kim_qbatch_ref

    cands, qs, _, _, mask = inputs
    got = np.asarray(lb_kim_qbatch_op(cands, qs, mask, p=1, interpret=interpret))
    want = np.asarray(lb_kim_qbatch_ref(cands, qs, mask, p=1))
    check(np.array_equal(got, want), "lb_kim differs from its oracle")
    return "bit-identical"


def _check_lb_keogh(inputs, interpret):
    from repro.kernels import lb_keogh_qbatch_op, lb_keogh_qbatch_ref

    cands, _, upper, lower, _ = inputs
    lb, h = lb_keogh_qbatch_op(cands, upper, lower, p=1, interpret=interpret)
    lb_ref, h_ref = lb_keogh_qbatch_ref(cands, upper, lower, p=1)
    lb, lb_ref = np.asarray(lb), np.asarray(lb_ref)
    check(np.array_equal(np.asarray(h), np.asarray(h_ref)), "projection H differs")
    err = float(np.max(np.abs(lb - lb_ref) / np.maximum(np.abs(lb_ref), 1e-30)))
    check(err <= 1e-4, f"lb_keogh rel err {err:.3e}")
    return f"H bit-identical, lb rel err {err:.1e}"


KERNEL_CHECKS = {"lb_keogh": _check_lb_keogh, "lb_kim": _check_lb_kim}


def phase_kernels(args, sizes, state):
    import jax

    from repro.kernels import TPU_READY

    check(set(TPU_READY) <= set(KERNEL_CHECKS), f"no check for {TPU_READY}")
    interpret = jax.default_backend() != "tpu"
    inputs = _kernel_inputs(sizes, args.seed)
    done = [f"{fam}: {KERNEL_CHECKS[fam](inputs, interpret)}" for fam in TPU_READY]
    return (
        f"interpret={interpret} B={sizes['kernel_b']} Q={sizes['kernel_q']} "
        f"n={sizes['length']}: " + "; ".join(done)
    )


def phase_sharded(args, sizes, state):
    import jax

    from repro.launch.mesh import make_host_mesh
    from repro.launch.serve import mixed_workload

    rows = args.chips * sizes["rows"]
    rng, data, db, line = build_session(
        rows, sizes["length"], args.seed, block=SHARDED_BLOCK
    )
    devs = jax.devices()[: args.chips]
    built = [peak_bytes(d) for d in devs]
    mesh = make_host_mesh()
    check(mesh.devices.size == args.chips, f"mesh of {mesh.devices.size} devices")
    db.use_mesh(mesh)
    qs = mixed_workload(rng, data, SHARDED_QUERIES)
    plan = db.plan(qs)
    check(plan.driver == "sharded", f"planner chose {plan.driver}, not sharded")
    t0 = time.perf_counter()
    sharded = db.search(qs)
    t_sharded = time.perf_counter() - t0
    peaks = [peak_bytes(d) for d in devs]
    if None not in peaks:
        check(
            max(peaks) < data.nbytes,
            f"a device peaked at {max(peaks)} bytes >= the database's "
            f"{data.nbytes}",
        )
    t0 = time.perf_counter()
    scan = db.search(qs, driver="scan")
    t_scan = time.perf_counter() - t0
    check(same_answers(sharded, scan), "sharded sweep and one-chip scan disagree")
    return (
        f"{line}; mesh={dict(zip(mesh.axis_names, mesh.devices.shape))}; "
        f"{len(qs)} queries sharded == one-chip scan bit for bit; "
        f"database {data.nbytes} bytes; peak_bytes_in_use per device "
        f"after build {built}, after the sharded sweep {peaks}, after the "
        f"one-chip scan {[peak_bytes(d) for d in devs]}; smoke timing: "
        f"sharded {t_sharded:.2f}s, scan {t_scan:.2f}s (compiles included)"
    )


# -------------------------------------------------------------------- main


def run_phase(name, fn, *fn_args) -> bool:
    t0 = time.perf_counter()
    try:
        line = fn(*fn_args)
    except Exception as e:  # report the phase, then stop at the first failure
        traceback.print_exc()
        print(f"FAIL {name}: {type(e).__name__}: {e}", flush=True)
        return False
    print(f"{name}: {line} [{time.perf_counter() - t0:.1f}s]", flush=True)
    return True


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded sweep vs the one-chip scan")
    ap.add_argument("--tiny", action="store_true",
                    help="rehearsal sizes; runs on the CPU but never reports ok")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    try:
        device_line, device = phase_device(args)
    except SmokeFailure as e:
        print(f"FAIL device: {e}", flush=True)
        return 1
    print(f"device: {device_line}", flush=True)
    sizes = TINY if args.tiny else FULL
    state: dict = {}
    if args.chips == 1:
        phases = [("build", phase_build), ("serve", phase_serve),
                  ("exact", phase_exact), ("kernels", phase_kernels)]
    else:
        phases = [("sharded", phase_sharded)]
    for name, fn in phases:
        if not run_phase(name, fn, args, sizes, state):
            return 1
    if device["platform"] != "tpu":
        print("rehearsal passed; no TPU, so no result is reported", flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
