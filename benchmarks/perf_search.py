"""§Perf measured hillclimb for the paper-representative cell: the
sharded two-pass DTW search, REAL wall times on this host (the search
engine actually runs here, unlike the TPU LM cells).

Knobs: sync_every (best-bound exchange cadence), block (vector lane
width of the cascade), method.  Run standalone:

    PYTHONPATH=src python -m benchmarks.perf_search
"""

from __future__ import annotations

import os
import time

import numpy as np

FAST = os.environ.get("REPRO_BENCH_FAST", "1") != "0"


def run(report=None):
    import jax
    from jax.sharding import AxisType, Mesh

    from repro.core.distributed import pad_database, sharded_nn_search
    from repro.data.synthetic import random_walks

    rng = np.random.default_rng(0)
    n_db, length = (2048, 256) if FAST else (16384, 1000)
    w = length // 10
    db = random_walks(rng, n_db, length)
    queries = random_walks(rng, 4, length)

    devs = np.array(jax.devices())
    if devs.size >= 8:
        mesh = Mesh(
            devs.reshape(2, 4), ("data", "model"), axis_types=(AxisType.Auto,) * 2
        )
    else:
        mesh = Mesh(devs.reshape(devs.size), ("data",), axis_types=(AxisType.Auto,))

    rows = []

    def bench(block, sync_every, method="lb_improved"):
        # bound executable-cache memory across variants
        from repro.core import distributed as _dist

        _dist._cached_fn.cache_clear()
        jax.clear_caches()
        dbp, _ = pad_database(db, mesh, block=block)
        # warm
        sharded_nn_search(queries[0], dbp, mesh, w=w, block=block,
                          sync_every=sync_every, method=method)
        t0 = time.perf_counter()
        stats = []
        for q in queries:
            res = sharded_nn_search(q, dbp, mesh, w=w, block=block,
                                    sync_every=sync_every, method=method)
            stats.append(res.stats)
        dt = (time.perf_counter() - t0) / len(queries)
        pruned = float(np.mean([s.pruning_ratio for s in stats]))
        dtw_done = int(np.mean([s.full_dtw for s in stats]))
        rows.append((method, block, sync_every, dt * 1e3, pruned, dtw_done))
        if report:
            report(
                f"perf_search/{method}/b{block}/s{sync_every}",
                dt * 1e6,
                f"pruned={100*pruned:.1f}% dtw={dtw_done}",
            )
        return dt, pruned, dtw_done

    for sync_every in (1, 4, 16):
        bench(32, sync_every)
    for block in (8, 64):
        bench(block, 1)
    bench(32, 1, method="lb_keogh")
    bench(32, 1, method="full")

    if report is None:
        print(f"{'method':<12} {'block':>5} {'sync':>7} {'ms/q':>8} {'pruned%':>8} {'dtw':>6}")
        for m, b, s, ms, p, d in rows:
            print(f"{m:<12} {b:>5} {s:>7} {ms:>8.1f} {100*p:>8.1f} {d:>6}")
    return rows


if __name__ == "__main__":
    run()
