"""k-NN search served by ``QueryEngine`` over one ``Database`` session.

Set-up makes the database and every query of the window from the seed,
builds the session and the engine, and runs one full engine batch of
other queries so that every program the window drives is compiled.  The
window is a closed loop: each client sends a query, waits for its
answer and sends the next.  Afterwards a sample of the answers, drawn
from the seed, is compared with the plain reference.
"""

from __future__ import annotations

import threading
import time

import numpy as np

from bench import generate
from bench.reference import knn as ref

#: how long a client waits for one answer before it counts as never come
ANSWER_TIMEOUT_S = 120.0


class Cell:
    """One k-NN cell: a session, its engine, and the window's queries."""

    def __init__(self, config: dict, traffic: dict, seed: int, seconds: float, spans):
        from repro.api import Database, SearchConfig
        from repro.serve import QueryEngine

        if traffic["loop"] != "closed":
            raise ValueError(f"knn serves closed-loop traffic, not {traffic['loop']!r}")
        self.config, self.traffic, self.seed = config, traffic, int(seed)
        self.spans = spans
        self.rows = generate.random_walks(
            generate.rng_for(seed, generate.DATA), config["rows"], config["length"]
        )
        count = int(np.ceil(traffic["max_qps"] * seconds)) + traffic["clients"]
        self.queries = generate.query_mix(
            generate.rng_for(seed, generate.TRAFFIC),
            self.rows,
            count,
            repeat_frac=traffic["repeat_frac"],
            near_frac=traffic["near_frac"],
            repeat_pool=traffic["repeat_pool"],
            near_sigma=traffic["near_sigma"],
        )
        self.db = Database.build(
            self.rows,
            SearchConfig(
                w=config["w"],
                p=config["p"],
                k=config["k"],
                block=config["block"],
                method=config["method"],
                znorm=config["znorm"],
                precision=config["precision"],
            ),
        )
        if self.db.w != config["w"]:
            raise ValueError(f"band resolved to {self.db.w}, config says {config['w']}")
        self.batches: list[tuple[float, float, int]] = []
        self._wrap_session_search()
        eng = config["engine"]
        self.engine = QueryEngine(
            self.db,
            max_batch=eng["max_batch"],
            max_wait_ms=eng["max_wait_ms"],
            max_queue=eng["max_queue"],
            cache_capacity=eng["cache_capacity"],
        )
        self.records: list[tuple[int, float, float, object]] = []
        self.counters: dict = {}

    def _wrap_session_search(self) -> None:
        """Record a span and the DP lanes of every batch the engine hands
        to the session (the engine's one call into the layers below)."""
        inner = self.db.search
        batches, spans = self.batches, self.spans

        def search(*args, **kwargs):
            with spans.span("session.search") as s:
                res = inner(*args, **kwargs)
            batches.append((s.t0, time.perf_counter(), int(res.stats.dp_lane_work)))
            return res

        self.db.search = search

    # ------------------------------------------------------------ set-up

    def warmup(self) -> None:
        """One full batch of cold walks that the window never sends."""
        n = self.config["engine"]["max_batch"]
        warm = generate.random_walks(
            generate.rng_for(self.seed, generate.WARMUP), n, self.config["length"]
        )
        futures = [self.engine.submit(q, tenant="warmup") for q in warm]
        for f in futures:
            f.result(timeout=None)
        self.batches.clear()

    # ------------------------------------------------------------ window

    def run_window(self, seconds: float) -> dict:
        engine, queries = self.engine, self.queries
        clients = self.traffic["clients"]
        lock = threading.Lock()
        state = {"next": 0, "exhausted": False}
        records = self.records
        before = engine.stats()
        t0 = time.perf_counter()
        t_stop = t0 + seconds

        def client(c: int) -> None:
            tenant = f"client{c}"
            while True:
                with lock:
                    if time.perf_counter() >= t_stop:
                        return
                    i = state["next"]
                    if i >= len(queries):
                        state["exhausted"] = True
                        return
                    state["next"] = i + 1
                t_sub = time.perf_counter()
                try:
                    fut = engine.submit(queries[i], tenant=tenant)
                    out = fut.result(timeout=ANSWER_TIMEOUT_S)
                except Exception as e:  # counted as failed, never dropped
                    out = e
                t_done = time.perf_counter()
                with lock:
                    records.append((i, t_sub, t_done, out))

        with self.spans.span("bench.window"):
            threads = [
                threading.Thread(target=client, args=(c,), name=f"client{c}")
                for c in range(clients)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        after = engine.stats()
        if state["exhausted"]:
            raise RuntimeError(
                f"the window used all {len(queries)} pre-generated queries; "
                f"raise max_qps in the traffic file"
            )
        records.sort(key=lambda r: r[0])
        answered = [r for r in records if not isinstance(r[3], Exception)]
        t_end = max(r[2] for r in records)
        window_s = t_end - t0
        lat_ms = np.array([1e3 * (r[2] - r[1]) for r in answered])
        executed = [
            r[3] for r in answered if not r[3].cache_hit and not r[3].coalesced
        ]
        served_batched = lambda s: s.served - s.cache_hits  # noqa: E731
        d_batched = served_batched(after) - served_batched(before)
        wait_sum = (
            after.wait_ms_mean * served_batched(after)
            - before.wait_ms_mean * served_batched(before)
        )
        self.counters = {
            "window_s": window_s,
            "t0": t0,
            "t_end": t_end,
            "answered": len(answered),
            "cache_hits": after.cache_hits - before.cache_hits,
            "cache_misses": after.cache_misses - before.cache_misses,
            "queue_wait_ms": wait_sum / d_batched if d_batched else None,
            "lanes_executed": len(executed),
            "full_dtw": sum(int(a.stats.full_dtw) for a in executed),
            "n_candidates": sum(int(a.stats.n_candidates) for a in executed),
            "batches": [b for b in self.batches if b[0] >= t0],
        }
        batch_ms = [1e3 * (b[1] - b[0]) for b in self.counters["batches"]]
        if batch_ms:
            self.counters.update(
                batch_ms_median=float(np.median(batch_ms)),
                batch_ms_max=max(batch_ms),
            )
        return {
            "attempted": len(records),
            "failed": len(records) - len(answered),
            "values": {
                "qps": len(answered) / window_s,
                "p95_ms": float(np.percentile(lat_ms, 95)) if lat_ms.size else float("inf"),
            },
        }

    # ------------------------------------------------------------ check

    def free(self) -> None:
        """Stop the engine and drop the session's device state."""
        self.engine.close()
        self.engine = None
        self.db = None

    def sample(self) -> list:
        answered = [r for r in self.records if not isinstance(r[3], Exception)]
        m = min(self.config["check"]["answers"], len(answered))
        pick = generate.rng_for(self.seed, generate.SAMPLE).choice(
            len(answered), m, replace=False
        )
        return [answered[i] for i in np.sort(pick)]

    def reference(self, picked: list, dtype: str) -> np.ndarray:
        cfg = self.config
        qs = np.stack([self.queries[r[0]] for r in picked])
        return ref.all_distances(
            qs,
            self.rows,
            cfg["w"],
            cfg["p"],
            dtype=dtype,
            pairs_per_call=cfg["check"]["pairs_per_call"],
        )

    def check(self) -> dict:
        """Every number compared, each with its limit."""
        cfg = self.config["check"]
        limits = cfg["limits"]
        picked = self.sample()
        unanswered = sum(isinstance(r[3], Exception) for r in self.records)
        nums = {"unanswered": float(unanswered)}
        if picked:
            dist = self.reference(picked, self.config["precision"])
            got_d = np.stack([r[3].distances for r in picked])
            got_i = np.stack([r[3].indices for r in picked])
            nums.update(ref.compare(got_d, got_i, dist))
        else:  # nothing answered: nothing can pass
            nums.update(dist_gap=float("inf"), index_gap=float("inf"))
        return {k: {"value": nums[k], "limit": limits[k]} for k in limits}

    def control(self, dtype: str) -> dict:
        """The reference in ``dtype`` put in the program's place: its own
        top-k of the sampled queries, compared as the program's are."""
        picked = self.sample()
        low = self.reference(picked, dtype)
        got_d, got_i = ref.topk(low, self.config["k"])
        dist = self.reference(picked, self.config["precision"])
        return ref.compare(got_d, got_i, dist)
