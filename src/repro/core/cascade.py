"""Two-pass pruned nearest-neighbour search — the paper's Algorithms 2/3.

The paper scans candidates one at a time, tightening a scalar best-so-far
``b``; each candidate passes through up to three stages::

    LB_Keogh  --prune?-->  LB_Improved pass 2  --prune?-->  full DTW

On a vector machine we process candidates in *blocks* (DESIGN.md §3.2)
and queries in *batches* (DESIGN.md §3.4): the scan carry is query-major,
holding one top-k per query lane, so a single sweep over the database
serves a whole `(Q, n)` query batch while every lane prunes against its
own tightening bound.

* ``nn_search_scan`` — fully jittable ``lax.scan`` over blocks.  Each
  block runs through the stage pipeline of ``repro.core.pipeline``
  (DESIGN.md §3.6): the first LB stage sweeps the whole tile, then every
  later stage runs survivor-compacted, so a fully-pruned block costs
  exactly one LB_Keogh pass — like the paper — and a barely-surviving
  block costs one LB pass plus a few compacted lane chunks instead of a
  full ``(Q, block)`` tile.  The carry threads the per-query top-k so
  later blocks see the tightened thresholds, preserving the sequential
  algorithm's pruning behaviour for every query independently.  A 1-D
  query returns a ``SearchResult``; a ``(Q, n)`` batch returns a
  ``BatchSearchResult``.
* ``nn_search_host`` — host-orchestrated variant with true survivor
  compaction: LB survivors are gathered into fixed-size chunks before the
  banded DTW runs, so wall-clock time tracks pruned work even when single
  lanes survive.  This is the implementation benchmarked against the
  paper's Figures 6-10.

Both return identical results (modulo distance ties) and per-stage
pruning statistics with the paper's per-candidate semantics; batched
search bit-matches the per-query loop (tests/test_batched_search.py).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Iterator

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.dtw import BIG, PNorm, finish_cost
from repro.core import pipeline as pipe
from repro.core.pipeline import Method, TriContext, run_block_stages
from repro.mv.dtw import dtw_qbatch_mv
from repro.mv.envelope import envelope_batch_mv

__all__ = [
    "BatchSearchResult",
    "Method",
    "SearchResult",
    "SearchStats",
    "nn_search_host",
    "nn_search_indexed",
    "nn_search_scan",
]


@dataclasses.dataclass(frozen=True)
class SearchStats:
    """Per-candidate stage counts (paper semantics: Figs 6-10 'pruning').

    ``stage_pruned`` carries one pruned count per LB stage the method's
    pipeline declared (``stage_names`` holds the matching registry
    names, in cascade order), so arbitrarily deep cascades are counted
    exactly; the invariant

    ``sum(stage_pruned) + full_dtw (+ lb0_pruned) == n_candidates``

    holds on every search path.  The historical two-slot view stays
    available read-only: ``lb1_pruned`` is the first stage's count and
    ``lb2_pruned`` the sum of every later stage's, so the documented
    ``lb1_pruned + lb2_pruned + full_dtw (+ lb0_pruned) ==
    n_candidates`` identity keeps holding verbatim.

    In a query batch the per-candidate counters stay per-query (each
    query lane decides prune/keep against its own bound — DESIGN.md
    §3.4) while the ``blocks_*`` counters are execution counts of the
    shared batched sweep, so a per-query stats object inside a batch
    reports the batch-level block counts.
    """

    n_candidates: int
    full_dtw: int  # candidates that reached the O(nw) DP
    stage_names: tuple[str, ...] = ()  # LB stages, cascade order
    stage_pruned: tuple[int, ...] = ()  # discarded per LB stage
    blocks_total: int = 0
    blocks_lb2: int = 0  # blocks where pass 2 actually executed
    blocks_dtw: int = 0  # blocks where the DP actually executed
    # DP lane economics (batch-level, like blocks_*): the banded DP runs
    # on survivor-compacted lane chunks (DESIGN.md §3.6), so `work` is
    # the lanes actually executed (chunk-padded) and `useful` the alive
    # lanes among them.  useful/work is the headline wasted-vs-useful
    # ratio; the all-or-nothing baseline would have spent
    # Q * block * blocks_dtw lanes instead.
    dp_lane_work: int = 0
    dp_lane_useful: int = 0
    # stage-0 triangle-index counters (nn_search_indexed only)
    lb0_pruned: int = 0  # discarded by LB_tri before any envelope work
    ref_dtw: int = 0  # exact DPs spent on references at query time (2R:
    #                   one band-w and one band-2w sweep per reference)
    clusters_total: int = 0
    clusters_pruned: int = 0  # clusters discarded wholesale at stage 0

    @property
    def lb1_pruned(self) -> int:
        """Back-compat view: candidates discarded by the first LB stage."""
        return int(self.stage_pruned[0]) if self.stage_pruned else 0

    @property
    def lb2_pruned(self) -> int:
        """Back-compat view: candidates discarded by every later LB stage."""
        return int(sum(self.stage_pruned[1:]))

    @property
    def pruned_by(self) -> dict[str, int]:
        """Per-stage pruned counts keyed by registry stage name."""
        return dict(zip(self.stage_names, self.stage_pruned))

    @property
    def pruning_ratio(self) -> float:
        if self.n_candidates == 0:
            return 0.0
        return 1.0 - self.full_dtw / self.n_candidates

    @property
    def stage0_ratio(self) -> float:
        """Fraction of candidates killed before any per-candidate LB work."""
        if self.n_candidates == 0:
            return 0.0
        return self.lb0_pruned / self.n_candidates

    @property
    def dp_lane_efficiency(self) -> float:
        """useful / work of the DP lanes actually executed (1.0 when the
        DP never ran): how much of the dispatched DP was not padding."""
        if self.dp_lane_work == 0:
            return 1.0
        return self.dp_lane_useful / self.dp_lane_work


@dataclasses.dataclass(frozen=True)
class SearchResult:
    distances: np.ndarray  # (k,) ascending
    indices: np.ndarray  # (k,)
    stats: SearchStats

    @property
    def distance(self) -> float:
        return float(self.distances[0])

    @property
    def index(self) -> int:
        return int(self.indices[0])


@dataclasses.dataclass(frozen=True)
class BatchSearchResult:
    """Results for a ``(Q, n)`` query batch (DESIGN.md §3.4).

    ``stats`` aggregates the per-candidate counters over the whole batch
    (``n_candidates = Q * n_db``); ``per_query[i]`` keeps the paper's
    per-candidate semantics for query ``i`` alone.  Indexing returns the
    per-query ``SearchResult``, so ``result[i]`` is interchangeable with
    what a per-query search call would have returned.
    """

    distances: np.ndarray  # (Q, k) ascending per row
    indices: np.ndarray  # (Q, k)
    stats: SearchStats  # aggregated over the batch
    per_query: tuple[SearchStats, ...] = ()

    def __len__(self) -> int:
        return int(self.distances.shape[0])

    def __getitem__(self, i: int) -> SearchResult:
        stats = self.per_query[i] if self.per_query else self.stats
        return SearchResult(
            distances=self.distances[i], indices=self.indices[i], stats=stats
        )

    def __iter__(self) -> Iterator[SearchResult]:
        return (self[i] for i in range(len(self)))


def _pad_db(db: jax.Array, block: int) -> tuple[jax.Array, int]:
    n_db = db.shape[0]
    n_pad = (-n_db) % block
    if n_pad:
        # pad rows never win: their LB vs any envelope is huge
        filler = jnp.full((n_pad, db.shape[1]), 0.5 * BIG ** 0.25, db.dtype)
        db = jnp.concatenate([db, filler], axis=0)
    return db, n_pad


def make_block_step(
    qs: jax.Array,
    upper: jax.Array,
    lower: jax.Array,
    w: int,
    p: PNorm,
    k: int,
    block: int,
    method: Method,
    masked: bool = False,
    n_real: jax.Array | None = None,
    d: int = 1,
    tri: TriContext | None = None,
):
    """Build the query-major scan body shared by local, sharded and
    indexed search (DESIGN.md §3.4).

    ``qs``, ``upper``, ``lower`` are ``(Q, d*n)`` — a query batch with
    its (per-channel-segment, for ``d > 1``) envelopes; a single query
    is the ``Q = 1`` special case.  ``tri`` optionally carries the
    reference-index context consumed by the ``tc_tri`` stage.

    carry = (top_v (Q, k), top_i (Q, k), gbound (Q,),
             stage_pruned (S, Q) — one row per LB stage of the method's
             pipeline, dtw_count (Q,),
             lb2_blocks, dtw_blocks, dp_lane_work, dp_lane_useful)
    input = (block_array, lane_indices[, entry_mask])
    where ``lane_indices`` is the (block,) vector of candidate ids — a
    contiguous range for the plain scan, a compacted survivor gather for
    ``nn_search_indexed`` — shared by every query lane, and ``entry_mask``
    (only when ``masked=True``) is a (Q, block) bool marking which lanes
    are still alive on entry (stage-0 survivors per query; masked-off
    lanes are neither evaluated nor counted).  When ``n_real`` is given
    instead, lanes with ``cand_i >= n_real`` (database pad rows) are
    masked off the same way without materializing a mask per step —
    pads' filler rows pass LB while a bound is still BIG, so they must
    never be counted.
    ``gbound`` is an externally-supplied per-query pruning bound (the
    sharded search pmin-exchanges it between rounds; local search leaves
    it at BIG).  All values powered (no l_p root).
    """
    nq = qs.shape[0]
    n_lb = len(pipe.lb_stage_names(method))

    def body(carry, inp):
        (top_v, top_i, gbound, c_stage, c_dtw,
         b_lb2, b_dtw, w_dp, u_dp) = carry
        if masked:
            blk, cand_i, mask0 = inp
        else:
            blk, cand_i = inp
            if n_real is None:
                mask0 = jnp.ones((nq, block), bool)
            else:
                mask0 = jnp.broadcast_to(
                    (cand_i < n_real)[None, :], (nq, block)
                )
        bound = jnp.minimum(top_v[:, -1], gbound)  # per-query k-th best

        st = run_block_stages(
            qs, upper, lower, w, p, method, blk, bound, mask0,
            d=d, cand_i=cand_i, tri=tri,
        )

        # merge block results into each query's running top-k
        all_v = jnp.concatenate([top_v, st.d], axis=1)
        all_i = jnp.concatenate(
            [top_i, jnp.broadcast_to(cand_i[None, :], (nq, block))], axis=1
        )
        neg_v, sel = jax.lax.top_k(-all_v, k)
        top_v = -neg_v
        top_i = jnp.take_along_axis(all_i, sel, axis=1)

        if n_lb:
            # masks[s] & ~masks[s+1]: lanes LB stage s+1 pruned (§3.6)
            c_stage += jnp.stack(
                [
                    jnp.sum(
                        st.masks[s] & ~st.masks[s + 1], axis=1,
                        dtype=jnp.int32,
                    )
                    for s in range(n_lb)
                ]
            )
        c_dtw += jnp.sum(st.masks[-1], axis=1, dtype=jnp.int32)
        b_lb2 += jnp.int32(st.need_lb2)
        b_dtw += jnp.int32(st.need_dtw)
        w_dp += st.dp_lane_work
        u_dp += st.dp_lane_useful
        return (top_v, top_i, gbound, c_stage, c_dtw,
                b_lb2, b_dtw, w_dp, u_dp), None

    return body


def init_carry(
    k: int,
    top_v: jax.Array | None = None,
    top_i: jax.Array | None = None,
    nq: int = 1,
    n_lb: int = 0,
):
    """Fresh query-major scan carry for ``nq`` query lanes and a
    pipeline with ``n_lb`` LB stages; optionally seeded with an
    already-known (Q, k) top-k (the indexed search seeds it with the
    exact reference distances)."""
    return (
        jnp.full((nq, k), BIG) if top_v is None else jnp.asarray(top_v),
        jnp.full((nq, k), -1, jnp.int32)
        if top_i is None
        else jnp.asarray(top_i, jnp.int32),
        jnp.full((nq,), BIG),
        jnp.zeros((n_lb, nq), jnp.int32),  # stage_pruned, one row/LB stage
        jnp.zeros((nq,), jnp.int32),
        jnp.int32(0),
        jnp.int32(0),
        jnp.int32(0),  # dp_lane_work
        jnp.int32(0),  # dp_lane_useful
    )


@functools.partial(
    jax.jit, static_argnames=("w", "p", "k", "block", "method", "d")
)
def _scan_search(
    qs: jax.Array,
    db: jax.Array,
    n_real: jax.Array,
    w: int,
    p: PNorm,
    k: int,
    block: int,
    method: Method,
    d: int = 1,
):
    nq, n_flat = qs.shape
    w = int(min(w, n_flat // d - 1))  # clamp against the per-channel length
    upper, lower = envelope_batch_mv(qs, w, d)
    nb = db.shape[0] // block
    blocks = db.reshape(nb, block, n_flat)
    # int32 like the carry's top-k ids, also under x64
    idx = jnp.arange(nb * block, dtype=jnp.int32).reshape(nb, block)
    # pad lanes (cand_i >= n_real) are masked inside the body, never
    # evaluated or counted — see make_block_step(n_real=...)
    body = make_block_step(
        qs, upper, lower, w, p, k, block, method, n_real=n_real, d=d
    )
    n_lb = len(pipe.lb_stage_names(method))
    carry, _ = jax.lax.scan(
        body, init_carry(k, nq=nq, n_lb=n_lb), (blocks, idx)
    )
    top_v, top_i, _gbound, cs, c3, b2, b3, w_dp, u_dp = carry
    return top_v, top_i, cs, c3, b2, b3, w_dp, u_dp


def _batch_stats(
    n_db: int,
    stage_names: tuple[str, ...],
    stage_pruned: np.ndarray,
    c3: np.ndarray,
    b2: int,
    b3: int,
    blocks_total: int,
    per_query_stage0: list[dict] | None = None,
    dp_lane_work: int = 0,
    dp_lane_useful: int = 0,
) -> tuple[SearchStats, tuple[SearchStats, ...]]:
    """Per-query + aggregated stats from the per-stage counter vectors.

    ``stage_pruned`` is (S, Q) — one row per LB stage of the method's
    pipeline, in ``stage_names`` order.  Every driver masks or slices
    padded lanes out of its counters, so no pad corrections are needed
    here.  ``per_query_stage0`` optionally carries each query's stage-0
    counter dict (lb0_pruned / ref_dtw / clusters_*) from the indexed
    path.  The DP lane counters are batch-level (survivor pairs are
    pooled across queries), so per-query stats carry the batch values,
    like ``blocks_*``.
    """
    nq = len(c3)
    stage_pruned = np.asarray(stage_pruned).reshape(len(stage_names), nq)
    s0_per = per_query_stage0 if per_query_stage0 is not None else [{}] * nq
    per_query = tuple(
        SearchStats(
            n_candidates=n_db,
            stage_names=tuple(stage_names),
            stage_pruned=tuple(int(v) for v in stage_pruned[:, i]),
            full_dtw=int(c3[i]),
            blocks_total=blocks_total,
            blocks_lb2=int(b2),
            blocks_dtw=int(b3),
            dp_lane_work=int(dp_lane_work),
            dp_lane_useful=int(dp_lane_useful),
            **s0_per[i],
        )
        for i in range(nq)
    )
    agg = SearchStats(
        n_candidates=nq * n_db,
        stage_names=tuple(stage_names),
        stage_pruned=tuple(int(v) for v in stage_pruned.sum(axis=1)),
        full_dtw=sum(s.full_dtw for s in per_query),
        blocks_total=blocks_total,
        blocks_lb2=int(b2),
        blocks_dtw=int(b3),
        dp_lane_work=int(dp_lane_work),
        dp_lane_useful=int(dp_lane_useful),
        lb0_pruned=sum(s.lb0_pruned for s in per_query),
        ref_dtw=sum(s.ref_dtw for s in per_query),
        clusters_total=sum(s.clusters_total for s in per_query),
        clusters_pruned=sum(s.clusters_pruned for s in per_query),
    )
    return agg, per_query


def nn_search_scan(
    q: jax.Array,
    db: jax.Array,
    w: int,
    p: PNorm = 1,
    k: int = 1,
    block: int = 32,
    method: Method = "lb_improved",
    d: int = 1,
) -> SearchResult | BatchSearchResult:
    """Jit-compiled block-scan cascade (device-resident end to end).

    ``q`` may be a single series (d*n,) -> ``SearchResult`` or a query
    batch (Q, d*n) -> ``BatchSearchResult``; the batch shares one sweep
    over the database (DESIGN.md §3.4) and bit-matches the per-query
    loop.  ``d > 1`` interprets rows as channel-major flattened
    multivariate series (repro.mv.layout).
    """
    q = jnp.asarray(q)
    single = q.ndim == 1
    qs = q[None, :] if single else q
    db = jnp.asarray(db)
    n_db = db.shape[0]
    dbp, _ = _pad_db(db, block)
    top_v, top_i, cs, c3, b2, b3, w_dp, u_dp = _scan_search(
        qs, dbp, jnp.int32(n_db), int(w), p, int(k), int(block), method,
        int(d),
    )
    agg, per_query = _batch_stats(
        n_db,
        pipe.lb_stage_names(method),
        np.asarray(cs),
        np.asarray(c3),
        int(b2),
        int(b3),
        blocks_total=dbp.shape[0] // block,
        dp_lane_work=int(w_dp),
        dp_lane_useful=int(u_dp),
    )
    distances = np.asarray(finish_cost(top_v, p))
    indices = np.asarray(top_i)
    if single:
        return SearchResult(
            distances=distances[0], indices=indices[0], stats=per_query[0]
        )
    return BatchSearchResult(
        distances=distances, indices=indices, stats=agg, per_query=per_query
    )


# ------------------------------------------------------------------ host


@functools.partial(jax.jit, static_argnames=("name", "w", "p", "d"))
def _dense_stage_qblock(name, qs, upper, lower, blk, w, p, d=1):
    """One registry stage's dense (Q, B) form — the host driver sweeps
    whatever LB stages the method's pipeline declares, so a new bound
    registered in ``repro.core.pipeline`` appears here for free."""
    ctx = pipe.PipeContext(qs, upper, lower, w, p, d=d)
    return pipe.STAGES[name].dense(ctx, blk)


@functools.partial(jax.jit, static_argnames=("w", "p", "d"))
def _dtw_pairs_block(qrows, crows, w, p, d=1):
    """Banded DP over explicit (query, candidate) row pairs — the pooled
    survivor chunks of the batched host cascade (DESIGN.md §3.4)."""
    if d > 1:
        from repro.mv.dtw import dtw_banded_diag_mv, dtw_banded_mv

        fn = dtw_banded_mv if p != jnp.inf else dtw_banded_diag_mv
        return jax.vmap(lambda a, b: fn(a, b, w, p, powered=True, d=d))(
            qrows, crows
        )
    from repro.core.dtw import dtw_banded, dtw_banded_diag

    fn = dtw_banded if p != jnp.inf else dtw_banded_diag
    return jax.vmap(lambda a, b: fn(a, b, w, p, powered=True))(qrows, crows)


@functools.partial(jax.jit, static_argnames=("w", "p", "d"))
def _dtw_pairs_block_early(qrows, crows, w, bounds, p, d=1):
    if d > 1:
        from repro.mv.dtw import dtw_banded_early_mv

        return jax.vmap(
            lambda a, b, bd: dtw_banded_early_mv(a, b, w, bd, p, d)
        )(qrows, crows, bounds)
    from repro.core.dtw import dtw_banded_early

    return jax.vmap(lambda a, b, bd: dtw_banded_early(a, b, w, bd, p))(
        qrows, crows, bounds
    )


@obs.spanned("session.host")
def nn_search_host(
    q: jax.Array,
    db: jax.Array,
    w: int,
    p: PNorm = 1,
    k: int = 1,
    block: int = 256,
    dtw_chunk: int = 16,
    method: Method = "lb_improved",
    early_abandon: bool = False,
    d: int = 1,
) -> SearchResult | BatchSearchResult:
    """Host-orchestrated cascade with survivor compaction.

    Device work: vectorised LB passes per block; banded DTW only on
    gathered survivors, padded to fixed ``dtw_chunk`` shapes so nothing
    recompiles.  Mirrors the paper's Algorithm 3 economics: time scales
    with (2N+3)n + 5(1-alpha)Nn + DTW(survivors).  ``early_abandon``
    additionally stops each DP once every band cell exceeds the running
    bound (paper §3 / the author's lbimproved library).

    ``q`` may be a single series (n,) -> ``SearchResult`` or a query
    batch (Q, n) -> ``BatchSearchResult``.  Batched, the LB passes serve
    every query lane per block in one dispatch and — the decisive part
    (DESIGN.md §3.4) — the per-(query, candidate) survivor pairs of the
    *whole batch* are pooled into shared ``dtw_chunk``-sized DP
    dispatches, so nearly-empty per-query chunks disappear and DP lanes
    track total surviving work, not query count.

    Spans (``repro.obs``): the call is ``session.host``; each block is a
    ``session.host.block`` whose leaves tile it, ``session.host.lb`` per
    LB stage, ``session.host.compact``, then ``session.host.dp`` and
    ``session.host.merge`` per DP chunk; each blocking device-to-host
    read is a ``session.host.wait`` inside the span that made it.
    """
    q = jnp.asarray(q)
    single = q.ndim == 1
    qs = q[None, :] if single else q
    nq = qs.shape[0]
    db_j = jnp.asarray(db)
    n_db, n = db_j.shape
    d = int(d)
    w = int(min(w, n // d - 1))  # clamp against the per-channel length
    upper, lower = envelope_batch_mv(qs, w, d)

    top_v = np.full((nq, k), BIG)
    top_i = np.full((nq, k), -1, np.int64)
    lb_names = pipe.lb_stage_names(method)
    lb_pruned = np.zeros((len(lb_names), nq), np.int64)  # per LB stage
    c3 = np.zeros(nq, np.int64)
    blocks_lb2 = blocks_dtw = 0
    dp_lane_work = dp_lane_useful = 0
    nb = -(-n_db // block)

    def merge(qi: int, vals: np.ndarray, idxs: np.ndarray):
        av = np.concatenate([top_v[qi], vals])
        ai = np.concatenate([top_i[qi], idxs])
        order = np.argsort(av, kind="stable")[:k]
        top_v[qi], top_i[qi] = av[order], ai[order]

    for t in range(nb):
        with obs.span("session.host.block"):
            lo, hi = t * block, min((t + 1) * block, n_db)
            blk = db_j[lo:hi]
            if blk.shape[0] < block:  # pad the tail block once
                pad = jnp.broadcast_to(blk[-1:], (block - blk.shape[0], n))
                blk = jnp.concatenate([blk, pad], axis=0)
            bound = top_v[:, -1]  # (Q,)

            # LB stages as the method's pipeline declares them: the first
            # sweeps the whole block, later ones only run while lanes survive
            alive = np.ones((nq, hi - lo), bool)
            for si, name in enumerate(lb_names):
                if si > 0:
                    if not alive.any():
                        break
                    if si == 1:  # once per block, however deep the cascade
                        blocks_lb2 += 1
                with obs.span("session.host.lb", stage=name):
                    lb = _dense_stage_qblock(name, qs, upper, lower, blk, w, p, d)
                    with obs.span("session.host.wait"):
                        lb = np.asarray(lb)
                    lb = lb[:, : hi - lo]
                    alive_next = alive & (lb < bound[:, None])
                    lb_pruned[si] += (alive & ~alive_next).sum(axis=1)
                    alive = alive_next

            # pooled survivor pairs: all queries' survivors of this block,
            # query-major order so each chunk touches few top-k rows
            with obs.span("session.host.compact"):
                pair_q, pair_c = np.nonzero(alive)
                pair_c = pair_c + lo
                c3 += alive.sum(axis=1)
            for s0 in range(0, len(pair_q), dtw_chunk):
                sel_q = pair_q[s0 : s0 + dtw_chunk]
                sel_c = pair_c[s0 : s0 + dtw_chunk]
                with obs.span("session.host.dp", pairs=dtw_chunk, useful=len(sel_q)):
                    pad_n = dtw_chunk - len(sel_q)
                    sel_qp = np.concatenate([sel_q, np.repeat(sel_q[-1:], pad_n)])
                    sel_cp = np.concatenate([sel_c, np.repeat(sel_c[-1:], pad_n)])
                    blocks_dtw += 1
                    dp_lane_work += dtw_chunk
                    dp_lane_useful += len(sel_q)
                    if early_abandon:
                        dvals = _dtw_pairs_block_early(
                            qs[sel_qp],
                            db_j[sel_cp],
                            w,
                            jnp.asarray(top_v[sel_qp, -1]),
                            p,
                            d,
                        )
                    else:
                        dvals = _dtw_pairs_block(qs[sel_qp], db_j[sel_cp], w, p, d)
                    with obs.span("session.host.wait"):
                        dvals = np.array(dvals)
                    if pad_n:
                        dvals[dtw_chunk - pad_n :] = BIG
                with obs.span("session.host.merge"):
                    for qi in np.unique(sel_qp):
                        sel = sel_qp == qi
                        merge(int(qi), dvals[sel], sel_cp[sel])

    agg, per_query = _batch_stats(
        n_db,
        lb_names,
        lb_pruned,
        c3,
        blocks_lb2,
        blocks_dtw,
        blocks_total=nb,
        dp_lane_work=dp_lane_work,
        dp_lane_useful=dp_lane_useful,
    )
    distances = finish_cost(jnp.asarray(top_v), p)
    with obs.span("session.host.wait"):
        distances = np.asarray(distances)
    if single:
        return SearchResult(
            distances=distances[0], indices=top_i[0], stats=per_query[0]
        )
    return BatchSearchResult(
        distances=distances, indices=top_i, stats=agg, per_query=per_query
    )


# --------------------------------------------------------------- indexed


@functools.partial(
    jax.jit, static_argnames=("w", "p", "k", "block", "method", "d")
)
def _scan_search_compact(
    qs: jax.Array,
    sub: jax.Array,
    idx: jax.Array,
    mask: jax.Array,
    top_v0: jax.Array,
    top_i0: jax.Array,
    w: int,
    p: PNorm,
    k: int,
    block: int,
    method: Method,
    d: int = 1,
    tri: TriContext | None = None,
):
    """Seeded block scan over a compacted survivor set (DESIGN.md §3.3).

    Same ``make_block_step`` body as ``_scan_search``, but candidate ids
    arrive as an explicit gather (``idx``), the top-k starts from the
    exact reference distances instead of BIG, and a (Q, total) entry
    ``mask`` keeps each query lane to its *own* stage-0 survivors — the
    compacted set is the union over the batch (§3.4), so a candidate
    another query still needs is swept once but never evaluated or
    counted for queries that already killed it.  ``tri`` (the
    reference-index context) reaches the ``tc_tri`` stage when the
    method's pipeline declares it.
    """
    nq, n_flat = qs.shape
    w = int(min(w, n_flat // d - 1))
    upper, lower = envelope_batch_mv(qs, w, d)
    nb = sub.shape[0] // block
    blocks = sub.reshape(nb, block, n_flat)
    idxb = idx.reshape(nb, block)
    maskb = jnp.transpose(mask.reshape(nq, nb, block), (1, 0, 2))
    body = make_block_step(
        qs, upper, lower, w, p, k, block, method, masked=True, d=d, tri=tri
    )
    n_lb = len(pipe.lb_stage_names(method))
    carry, _ = jax.lax.scan(
        body,
        init_carry(k, top_v0, top_i0, nq=nq, n_lb=n_lb),
        (blocks, idxb, maskb),
    )
    top_v, top_i, _gbound, cs, c3, b2, b3, w_dp, u_dp = carry
    return top_v, top_i, cs, c3, b2, b3, w_dp, u_dp


def nn_search_indexed(
    q: jax.Array,
    db: jax.Array,
    index,
    k: int = 1,
    block: int = 32,
    method: Method = "lb_improved",
) -> SearchResult | BatchSearchResult:
    """Four-stage search: LB_tri -> LB_Keogh -> LB_Improved -> DTW.

    ``index`` is a prebuilt ``repro.index.TriangleIndex`` over ``db``;
    ``w`` and ``p`` come from the index (Theorem 1's constant depends on
    both, so they are baked in at build time).  ``q`` may be a single
    series (n,) -> ``SearchResult`` or a query batch (Q, n) ->
    ``BatchSearchResult``: stage 0 runs once for the whole batch (2R DPs
    *per query*, batched into two dispatches) and stages 1-3 sweep the
    union of the per-query survivor sets with per-lane entry masks
    (DESIGN.md §3.4).

    Stage 0 spends 2R exact DTWs per query on the reference series (band
    w and the composed band 2w — the two sides of the banded triangle
    inequality consume different bands, see repro.index.triangle_lb).
    References are database members, so the band-w distances seed the
    top-k with *true* distances; then whole clusters and individual
    candidates die with O(R) arithmetic per candidate before any envelope
    work.  Survivors are compacted and swept by the usual block cascade
    (``make_block_step``), padded to a power-of-two number of blocks so
    jit specialisations stay logarithmic in database size.

    Stats fields (``SearchStats``) specific to this path:

    * ``lb0_pruned`` — candidates killed by LB_tri / cluster bounds at
      stage 0, before any envelope work;
    * ``ref_dtw`` — 2R: the exact reference DPs spent at query time (the
      band-w sweep and the band-2w sweep);
    * ``clusters_total`` / ``clusters_pruned`` — cluster-granularity
      prune counts (a pruned cluster kills all its members in O(1));
    * ``full_dtw`` *includes* the R band-w reference DPs, since those are
      true candidate distances (they seed the top-k), so the invariant
      ``lb0 + lb1 + lb2 + full_dtw == n_candidates`` holds per query.
    """
    from repro.index.triangle_lb import (
        lb_triangle_batch,
        lb_triangle_clusters,
        powered,
    )

    q = jnp.asarray(q)
    single = q.ndim == 1
    qs = q[None, :] if single else q
    nq = qs.shape[0]
    db_j = jnp.asarray(db)
    n_db, n = db_j.shape
    w, p = index.w, (jnp.inf if np.isinf(index.p) else index.p)
    if p != jnp.inf and float(p) == int(p):
        p = int(p)
    d = int(getattr(index, "d", 1))
    index.validate(n_db, n // d, w, p, d)
    cl = index.clustering
    c_w = index.constant
    n_refs = index.n_refs
    dev = index.device_arrays  # build-time constants, uploaded once

    # cheap guard against serving a different database of the same shape
    # (stale indexes would silently prune true neighbours): O(R*n)
    ref_rows = np.asarray(db_j[jnp.asarray(index.ref_idx)], np.float32)
    if not np.array_equal(ref_rows, np.asarray(index.ref_series, np.float32)):
        raise ValueError(
            "database rows at ref_idx do not match the index's reference "
            "series — the index belongs to a different database"
        )

    # ---- stage 0a: exact DTW to the references at both bands (2R DPs
    #      per query, batched over the whole query block)
    refs_j = dev["ref_series"]
    d_q_refs = np.asarray(dtw_qbatch_mv(qs, refs_j, w, p, powered=False, d=d))
    d_q_refs_wide = np.asarray(
        dtw_qbatch_mv(qs, refs_j, index.w_wide, p, powered=False, d=d)
    )
    # ``powered`` is elementwise python arithmetic — it works on numpy
    # arrays directly, no device round-trip needed for stage-0 scalars
    ref_pow = powered(d_q_refs, p)  # (Q, R)
    order = np.argsort(ref_pow, axis=1, kind="stable")
    top_v = np.full((nq, k), BIG)
    top_i = np.full((nq, k), -1, np.int64)
    m = min(k, n_refs)
    top_v[:, :m] = np.take_along_axis(ref_pow, order[:, :m], axis=1)
    top_i[:, :m] = np.asarray(index.ref_idx)[order[:, :m]]
    bound = top_v[:, -1]  # (Q,) powered k-th best so far

    # ---- stage 0b: cluster-granularity pruning (O(C) work per query)
    cl_lb = np.asarray(
        lb_triangle_clusters(
            jnp.asarray(d_q_refs[:, cl.rep_rows]),
            jnp.asarray(d_q_refs_wide[:, cl.rep_rows]),
            dev["radii"],
            dev["min_radii_wide"],
            c_w,
        )
    )
    cl_alive = powered(cl_lb, p) < bound[:, None]  # (Q, C)
    alive = cl_alive[:, cl.assign]  # (Q, N)

    # ---- stage 0c: per-candidate LB_tri over all references (O(R) each)
    lb0 = np.asarray(
        lb_triangle_batch(
            jnp.asarray(d_q_refs),
            jnp.asarray(d_q_refs_wide),
            dev["d_ref_db"],
            dev["d_ref_db_wide"],
            c_w,
        )
    )
    alive &= powered(lb0, p) < bound[:, None]
    alive[:, index.ref_idx] = False  # references were evaluated exactly above
    per_q_survivors = alive.sum(axis=1)  # (Q,)
    lb0_pruned = n_db - n_refs - per_q_survivors
    # stages 1-3 sweep the union of the per-query survivor sets once;
    # the per-lane entry mask keeps each query to its own survivors
    survivors = np.nonzero(alive.any(axis=0))[0]

    stage0_per = [
        dict(
            lb0_pruned=int(lb0_pruned[i]),
            ref_dtw=2 * n_refs,
            clusters_total=cl.n_clusters,
            clusters_pruned=int((~cl_alive[i]).sum()),
        )
        for i in range(nq)
    ]

    def finish(top_v_arr, top_i_arr, agg, per_query):
        distances = np.asarray(finish_cost(jnp.asarray(top_v_arr), p))
        indices = np.asarray(top_i_arr)
        if single:
            return SearchResult(
                distances=distances[0], indices=indices[0], stats=per_query[0]
            )
        return BatchSearchResult(
            distances=distances,
            indices=indices,
            stats=agg,
            per_query=per_query,
        )

    lb_names = pipe.lb_stage_names(method)
    if len(survivors) == 0:
        agg, per_query = _batch_stats(
            n_db,
            lb_names,
            np.zeros((len(lb_names), nq), np.int64),
            np.full(nq, n_refs, np.int64),
            0,
            0,
            blocks_total=0,
            per_query_stage0=stage0_per,
        )
        return finish(top_v, top_i, agg, per_query)

    # ---- stages 1-3: compacted block cascade over the survivor union
    nb = -(-len(survivors) // block)
    nb_pad = 1 << (nb - 1).bit_length()  # power-of-two block count
    total = nb_pad * block
    pad = total - len(survivors)
    sub = db_j[jnp.asarray(survivors)]
    if pad:
        filler = jnp.full((pad, n), 0.5 * BIG ** 0.25, db_j.dtype)
        sub = jnp.concatenate([sub, filler], axis=0)
    idx = np.concatenate([survivors, np.full((pad,), -1, np.int64)])
    # (Q, total) entry mask: each lane alive only for queries that still
    # need it; padded filler lanes are dead for everyone
    mask = np.concatenate(
        [alive[:, survivors], np.zeros((nq, pad), bool)], axis=1
    )
    # pipelines declaring tc_tri re-apply LB_tri per block against the
    # *running* top-k bound (stage 0 above only saw the initial
    # reference-seeded bound), so the reference context rides along
    tri = None
    if "tc_tri" in pipe.PIPELINES[method]:
        tri = TriContext(
            d_q_refs=jnp.asarray(d_q_refs),
            d_q_refs_wide=jnp.asarray(d_q_refs_wide),
            d_ref_db=dev["d_ref_db"],
            d_ref_db_wide=dev["d_ref_db_wide"],
            c_w=jnp.asarray(c_w),
        )
    top_vj, top_ij, cs, c3, b2, b3, w_dp, u_dp = _scan_search_compact(
        qs,
        sub,
        jnp.asarray(idx, jnp.int32),
        jnp.asarray(mask),
        jnp.asarray(top_v),
        jnp.asarray(top_i, jnp.int32),
        int(w),
        p,
        int(k),
        int(block),
        method,
        d,
        tri,
    )
    # masked lanes (stage-0 pruned and padded) are neither evaluated nor
    # counted, so no pad correction is needed; the R band-w reference DPs
    # count as full_dtw (they seed the top-k with true distances)
    agg, per_query = _batch_stats(
        n_db,
        lb_names,
        np.asarray(cs),
        np.asarray(c3) + n_refs,
        int(b2),
        int(b3),
        blocks_total=nb_pad,
        per_query_stage0=stage0_per,
        dp_lane_work=int(w_dp),
        dp_lane_useful=int(u_dp),
    )
    return finish(top_vj, top_ij, agg, per_query)
