"""Distributed DTW search service (the paper's system, served async).

Runs with 8 virtual host devices to demonstrate the full serving stack:
one ``repro.api.Database`` session is built (artifacts computed once), a
mesh is attached so the planner routes onto the sharded driver, and a
``repro.serve.QueryEngine`` serves two concurrent tenants — admission
queues, round-robin microbatch coalescing (DESIGN.md §3.8, executing
through the §3.4 query-major sweeps), and an answer cache that serves
the repeated query without touching the cascade.  Every answer is
checked bit-identical against the same session's single-device scan.

    PYTHONPATH=src python examples/search_service.py
"""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import threading  # noqa: E402
import time  # noqa: E402

import jax  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh  # noqa: E402

from repro.api import Database, SearchConfig  # noqa: E402
from repro.data.synthetic import random_walks  # noqa: E402
from repro.serve import QueryEngine  # noqa: E402

rng = np.random.default_rng(0)
data = random_walks(rng, 2048, 256)
queries = random_walks(rng, 10, 256)

db = Database.build(data, SearchConfig(w=25, block=16))
devs = np.array(jax.devices())
mesh = Mesh(
    devs.reshape(2, 4), ("data", "model"),
    axis_types=(jax.sharding.AxisType.Auto,) * 2,
)
db.use_mesh(mesh, sync_every=4)
print(f"mesh {dict(zip(mesh.axis_names, mesh.devices.shape))}, db {db.n_rows} series")
print(db.plan(queries).explain())

# reference answers from the same session's single-device scan
local = db.search(queries, driver="scan")

engine = QueryEngine(db, max_batch=4, max_wait_ms=2.0, cache_capacity=32)

# two tenants submit concurrently; the coalescer drains them round-robin
# into shared sharded sweeps (no hand-rolled queue loop: admission and
# batching are the engine's job now)
results: dict[int, object] = {}


def tenant(name: str, idxs: list[int]) -> None:
    futures = [(qi, engine.submit(queries[qi], tenant=name)) for qi in idxs]
    for qi, fut in futures:
        results[qi] = fut.result()


t0 = time.perf_counter()
threads = [
    threading.Thread(target=tenant, args=("web", list(range(0, 10, 2)))),
    threading.Thread(target=tenant, args=("batch", list(range(1, 10, 2)))),
]
for t in threads:
    t.start()
for t in threads:
    t.join()
dt = time.perf_counter() - t0

for qi in range(len(queries)):
    res = results[qi]
    assert np.array_equal(res.distances, local.distances[qi]), qi
    assert np.array_equal(res.indices, local.indices[qi]), qi
    s = res.stats
    print(
        f"query {qi} [{res.tenant}]: nn=#{res.index} dist={res.distance:.2f} "
        f"dtw_lanes={s.full_dtw:4d} pruned={100 * s.pruning_ratio:.1f}% "
        f"lanes={res.batch_lanes} wait={res.wait_ms:.1f}ms"
    )

# the repeated query is answered from the cache: zero cascade work
hit = engine.search(queries[3], tenant="web")
assert hit.cache_hit and np.array_equal(hit.distances, local.distances[3])

s = engine.stats()
print(
    f"served {len(queries)} queries from 2 tenants in {dt * 1e3:.1f} ms "
    f"({len(queries) / dt:.1f} queries/sec): batches={s.batches} "
    f"occupancy={s.batch_occupancy:.2f} cache_hits={s.cache_hits} "
    f"coalesced={s.coalesced}; all answers match the single-device scan."
)
engine.close()
