"""Sharded DTW nearest-neighbour search — the paper's parallel postscript.

The paper's conclusion: *"Several instances of Algo. 3 can run in parallel
as long as they can communicate the distance between the time series and
the best candidate."*  This module turns that sentence into a mesh
program:

* the candidate database shards over (any subset of) the mesh axes;
* every shard runs the same query-major block cascade on its local
  stream — a whole ``(Q, n)`` query batch shares each sweep
  (DESIGN.md §3.4);
* every ``sync_every`` blocks the k-th-best *bound* is exchanged (a
  min over an all-gather) so all shards prune against the globally tightest
  threshold — one scalar **per query lane** over the ICI (the paper's
  "communicate the distance", vectorised over the batch);
* at the end local per-query top-k lists are all-gathered and merged.

``sync_every`` trades pruning power against collective latency; it is one
of the §Perf hillclimb knobs (EXPERIMENTS.md).
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.cascade import (
    BatchSearchResult,
    Method,
    SearchResult,
    _batch_stats,
    init_carry,
    make_block_step,
)
from repro.core.dtw import BIG, PNorm, finish_cost
from repro.core import pipeline as pipe
from repro.mv.envelope import envelope_batch_mv


def _sharded_search_fn(
    mesh: Mesh,
    axis_names: tuple[str, ...],
    w: int,
    p: PNorm,
    k: int,
    block: int,
    sync_every: int,
    method: Method,
    d: int = 1,
):
    """Build the jitted shard_map search: (qs, db_sharded) -> (top_v, top_i, stats).

    ``qs`` is the (Q, n) query batch, replicated to every shard; the
    carry is query-major so all Q lanes share each block sweep.
    """

    db_spec = P(axis_names)  # shard candidate axis over all given mesh axes

    def local_search(qs, db_local):
        nq, n = qs.shape  # n is the flat (d*n_per_channel) length
        upper, lower = envelope_batch_mv(qs, w, d)
        n_local = db_local.shape[0]
        nb = n_local // block
        shard_id = jnp.int32(0)
        stride = 1
        for ax in reversed(axis_names):
            shard_id = shard_id + jax.lax.axis_index(ax) * stride
            stride *= mesh.shape[ax]
        # int32 like the carry's top-k ids, also under x64
        local_ids = jnp.arange(n_local, dtype=jnp.int32).reshape(nb, block)
        idx = shard_id * n_local + local_ids
        blocks = db_local.reshape(nb, block, n)

        body = make_block_step(qs, upper, lower, w, p, k, block, method, d=d)

        rounds = -(-nb // sync_every)
        pad_rounds = rounds * sync_every - nb
        if pad_rounds:
            # replicate a poison block (top-k ignores BIG) to even rounds
            poison = jnp.full((pad_rounds, block, n), 0.5 * BIG ** 0.25)
            blocks = jnp.concatenate([blocks, poison], axis=0)
            idx = jnp.concatenate(
                [idx, jnp.full((pad_rounds, block), n_local * 10**6, jnp.int32)]
            )
        blocks = blocks.reshape(rounds, sync_every, block, n)
        idx = idx.reshape(rounds, sync_every, block)

        # The block step prunes against min(local k-th best, gbound); the
        # gbound slot of the carry is min-exchanged once per round (one
        # scalar per query lane over the ICI — the paper's "communicate
        # the distance", vectorised over the batch).
        def round_body(carry, inp):
            carry, _ = jax.lax.scan(body, carry, inp)
            top_v, top_i, gbound, *stats = carry
            gbound = jnp.minimum(gbound, top_v[:, -1])
            # min over an all-gather, not lax.pmin: the TPU lowers no
            # float64 min all-reduce (only sums), and the min is exact
            gbound = jnp.min(jax.lax.all_gather(gbound, axis_names), axis=0)
            return (top_v, top_i, gbound, *stats), None

        carry, _ = jax.lax.scan(
            round_body,
            init_carry(k, nq=nq, n_lb=len(pipe.lb_stage_names(method))),
            (blocks, idx),
        )
        top_v, top_i, _gbound, cs, c3, b2, b3, w_dp, u_dp = carry
        # gather per-shard per-query top-k along the k axis and merge
        all_v = jax.lax.all_gather(top_v, axis_names, axis=1, tiled=True)
        all_i = jax.lax.all_gather(top_i, axis_names, axis=1, tiled=True)
        neg, sel = jax.lax.top_k(-all_v, k)
        merged_i = jnp.take_along_axis(all_i, sel, axis=1)
        # (S+1, Q) per-query candidate counters: one row per LB stage,
        # then the DP row — summed over shards
        cand_stats = jnp.concatenate(
            [
                jax.lax.psum(cs, axis_names),
                jax.lax.psum(c3, axis_names)[None, :],
            ],
            axis=0,
        )
        block_stats = jnp.stack(  # summed over shards, like blocks_total
            [
                jax.lax.psum(b2, axis_names),
                jax.lax.psum(b3, axis_names),
                jax.lax.psum(w_dp, axis_names),
                jax.lax.psum(u_dp, axis_names),
            ]
        )
        return -neg, merged_i, cand_stats, block_stats

    fn = jax.shard_map(
        local_search,
        mesh=mesh,
        in_specs=(P(), db_spec),
        out_specs=(P(), P(), P(), P()),
        check_vma=False,
    )
    return jax.jit(fn)


@functools.lru_cache(maxsize=64)
def _cached_fn(mesh, axis_names, w, p, k, block, sync_every, method, d=1):
    return _sharded_search_fn(
        mesh, axis_names, w, p, k, block, sync_every, method, d
    )


def sharded_nn_search(
    q,
    db,
    mesh: Mesh,
    axis_names: Sequence[str] | None = None,
    w: int = 0,
    p: PNorm = 1,
    k: int = 1,
    block: int = 32,
    sync_every: int = 4,
    method: Method = "lb_improved",
    d: int = 1,
) -> SearchResult | BatchSearchResult:
    """Search a database sharded over ``mesh`` axes.

    ``q`` may be a single series (n,) -> ``SearchResult`` or a query
    batch (Q, n) -> ``BatchSearchResult``; the whole batch rides one
    sharded sweep and one bound-exchange lane per query (DESIGN.md §3.4).
    ``db`` rows must divide evenly by (shards * block); callers pad with
    ``pad_database``.
    """
    axis_names = tuple(axis_names if axis_names is not None else mesh.axis_names)
    q = jnp.asarray(q)
    single = q.ndim == 1
    qs = q[None, :] if single else q
    d = int(d)
    n = qs.shape[1]
    w = int(min(w, n // d - 1))
    fn = _cached_fn(
        mesh, axis_names, w, p, int(k), int(block), int(sync_every), method, d
    )
    db = jax.device_put(
        db, NamedSharding(mesh, P(axis_names))
    )
    top_v, top_i, cand_stats, block_stats = fn(qs, db)
    cand_stats = np.asarray(cand_stats)
    b2, b3, w_dp, u_dp = (int(v) for v in np.asarray(block_stats))
    lb_names = pipe.lb_stage_names(method)
    agg, per_query = _batch_stats(
        int(db.shape[0]),
        lb_names,
        cand_stats[: len(lb_names)],
        cand_stats[-1],
        b2,
        b3,
        blocks_total=int(db.shape[0]) // block,
        dp_lane_work=w_dp,
        dp_lane_useful=u_dp,
    )
    distances = np.asarray(finish_cost(jnp.asarray(top_v), p))
    indices = np.asarray(top_i)
    if single:
        return SearchResult(
            distances=distances[0], indices=indices[0], stats=per_query[0]
        )
    return BatchSearchResult(
        distances=distances, indices=indices, stats=agg, per_query=per_query
    )


def pad_database(db: np.ndarray, mesh: Mesh, axis_names=None, block: int = 32):
    """Pad rows so the DB divides by shards*block; returns (db, n_real)."""
    axis_names = tuple(axis_names if axis_names is not None else mesh.axis_names)
    shards = int(np.prod([mesh.shape[a] for a in axis_names]))
    mult = shards * block
    n = db.shape[0]
    n_pad = (-n) % mult
    if n_pad:
        filler = np.full((n_pad, db.shape[1]), 0.5 * BIG ** 0.25, db.dtype)
        db = np.concatenate([db, filler], axis=0)
    return db, n
