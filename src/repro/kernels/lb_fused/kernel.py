"""Pallas TPU kernel: fused LB_Keogh -> LB_Improved cascade stage.

The separate lb_keogh / lb_improved kernels stream the candidate block
out of HBM, write the (Q, B, n) projection stack H back to HBM, and read
it again for pass 2 — up to three HBM sweeps of block-sized data for one
cascade stage.  This kernel performs the whole two-pass bound while the
candidate tile is resident in VMEM:

    lb1   = || c - H(c, q) ||_p^p            (pass 1, Corollary 3)
    alive = lb1 < bound                       (per-lane predication)
    lb2   = || q - clip(q, L(H), U(H)) ||_p^p (pass 2, Corollary 4)
    lb    = alive ? lb1 + lb2 : lb1

H never leaves VMEM and only two scalars per lane return.  ``bound`` is
the query lane's powered pruning bound (the cascade's running k-th best
/ stream threshold): pass 2 is predicated on it per lane — dead lanes
contribute nothing to the output — and skipped outright (``lax.cond``)
when a tile has no survivor, so a fully-pruned tile costs exactly
pass 1, the paper's Algorithm 3 economics.  (On a VPU, per-lane *work*
skipping inside a live tile is the job of the survivor compaction
upstream — ``repro.core.pipeline`` — the kernel's contribution is
fusing the HBM traffic and the tile-granular skip.)

The pass-2 envelope U(H), L(H) is built in-kernel with the same vHGW
block trick as the lb_improved kernel: sentinel-pad the projection to a
multiple of the window, per-block prefix/suffix cummax/cummin, two
lookups per element.  Supports p in {1, 2} like the other kernels.

Schedules (DESIGN.md §3.11) — all bit-identical, resolved by the tune
table:

* ``grid="qb"``   — grid (Q, B/tile_b), candidate tiles innermost; each
  tile is streamed from HBM once **per query lane** (the PR 4 layout).
* ``grid="bq"``   — grid (B/tile_b, Q), query lanes innermost; each
  candidate tile is read from HBM **once total** and reused across the
  whole query batch while resident in VMEM.
* ``depth=1``     — single-buffered BlockSpec pipeline.
* ``depth=2``     — two-slot VMEM staging driven by explicit async
  copies: the DMA for tile t+1 is started before tile t's compute, so
  the next HBM->VMEM transfer overlaps the current tile's VPU work.
  In the ``bq`` layout only the ``qi == 0`` step of each tile column
  starts/waits a copy — one copy and one wait per tile, total.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import (
    BIG,
    cummax_doubling,
    cummin_doubling,
    round_up,
)


def _fused_tile_compute(c, u, l, q, bound, *, w: int, n: int, p):
    """Both passes on one resident (tile_b, n) candidate tile.

    Pure function of the tile values — every schedule variant funnels
    through here, which is the bit-identity argument in code form.
    ``bound`` is the lane's (1, 1) powered pruning bound.  Returns
    (lb1, lb) as (tile_b, 1) columns.
    """
    win = 2 * w + 1
    total = round_up(n + 2 * w, win)
    tile_b = c.shape[0]
    nblocks = total // win

    # ---- pass 1: clamp-project-accumulate (lb_keogh kernel, inlined)
    over = jnp.maximum(c - u, 0.0)
    under = jnp.maximum(l - c, 0.0)
    d1 = over + under  # one side is always 0
    cost1 = d1 if p == 1 else d1 * d1
    lb1 = jnp.sum(cost1, axis=1, keepdims=True)  # (tile_b, 1)

    alive = lb1 < bound  # per-lane predication of pass 2

    def pass2(_):
        h = jnp.clip(c, l, u)  # H(c, q) — VMEM only, never HBM

        def padded(x, fill):
            lo = jnp.full((tile_b, w), fill, x.dtype)
            hi = jnp.full((tile_b, total - n - w), fill, x.dtype)
            return jnp.concatenate([lo, x, hi], axis=1)

        bmax = padded(h, -BIG).reshape(tile_b * nblocks, win)
        bmin = padded(h, BIG).reshape(tile_b * nblocks, win)
        pref_max = cummax_doubling(bmax, axis=1).reshape(tile_b, total)
        suff_max = cummax_doubling(bmax[:, ::-1], axis=1)[:, ::-1].reshape(
            tile_b, total
        )
        pref_min = cummin_doubling(bmin, axis=1).reshape(tile_b, total)
        suff_min = cummin_doubling(bmin[:, ::-1], axis=1)[:, ::-1].reshape(
            tile_b, total
        )
        hu = jnp.maximum(suff_max[:, :n], pref_max[:, win - 1 : win - 1 + n])
        hl = jnp.minimum(suff_min[:, :n], pref_min[:, win - 1 : win - 1 + n])

        over2 = jnp.maximum(q - hu, 0.0)
        under2 = jnp.maximum(hl - q, 0.0)
        d2 = over2 + under2
        cost2 = d2 if p == 1 else d2 * d2
        return jnp.sum(cost2, axis=1, keepdims=True)  # (tile_b, 1)

    # tile-granular skip: a fully-pruned tile pays pass 1 only
    lb2 = jax.lax.cond(
        jnp.any(alive), pass2, lambda _: jnp.zeros_like(lb1), None
    )
    return lb1, jnp.where(alive, lb1 + lb2, lb1)


def _lb_fused_kernel(
    c_ref, u_ref, l_ref, q_ref, bound_ref, lb1_ref, lb_ref, *, w: int, n: int, p
):
    """depth=1: the candidate tile arrives via the BlockSpec pipeline."""
    lb1, lb = _fused_tile_compute(
        c_ref[...], u_ref[...], l_ref[...], q_ref[...], bound_ref[...],
        w=w, n=n, p=p,
    )
    lb1_ref[...] = lb1  # (tile_b, 1)
    lb_ref[...] = lb


def _lb_fused_db_qb_kernel(
    c_hbm, u_ref, l_ref, q_ref, bound_ref, lb1_ref, lb_ref, c_vmem, sem,
    *, w: int, n: int, p, tile_b: int,
):
    """depth=2, grid (Q, B/tile_b): two-slot staging, one copy per step.

    Linear step g = qi * nbt + bi walks tiles innermost; slot g % 2
    holds step g's tile, and step g starts the DMA for step g + 1
    before waiting on its own, so the next transfer rides under this
    tile's compute.  Exactly one wait per started copy.
    """
    qi, bi = pl.program_id(0), pl.program_id(1)
    nq, nbt = pl.num_programs(0), pl.num_programs(1)
    g = qi * nbt + bi

    def dma(slot, tile):
        return pltpu.make_async_copy(
            c_hbm.at[pl.ds(tile * tile_b, tile_b), :],
            c_vmem.at[slot],
            sem.at[slot],
        )

    @pl.when(g == 0)
    def _():
        dma(0, 0).start()

    # slot (g+1) % 2 belonged to step g-1, whose compute has retired
    # (the TPU grid is sequential), so overwriting it is safe
    @pl.when(g + 1 < nq * nbt)
    def _():
        dma((g + 1) % 2, (g + 1) % nbt).start()

    dma(g % 2, bi).wait()
    lb1, lb = _fused_tile_compute(
        c_vmem[g % 2], u_ref[...], l_ref[...], q_ref[...], bound_ref[...],
        w=w, n=n, p=p,
    )
    lb1_ref[...] = lb1
    lb_ref[...] = lb


def _lb_fused_db_bq_kernel(
    c_hbm, u_ref, l_ref, q_ref, bound_ref, lb1_ref, lb_ref, c_vmem, sem,
    *, w: int, n: int, p, tile_b: int,
):
    """depth=2, grid (B/tile_b, Q): one HBM read per tile, total.

    Query lanes iterate innermost, so tile bi stays resident in slot
    bi % 2 for all Q steps of its column; only the qi == 0 step copies
    (and prefetches column bi + 1).  HBM traffic for the candidate
    block drops from Q reads to one.
    """
    bi, qi = pl.program_id(0), pl.program_id(1)
    nbt, nq = pl.num_programs(0), pl.num_programs(1)

    def dma(slot, tile):
        return pltpu.make_async_copy(
            c_hbm.at[pl.ds(tile * tile_b, tile_b), :],
            c_vmem.at[slot],
            sem.at[slot],
        )

    @pl.when((bi == 0) & (qi == 0))
    def _():
        dma(0, 0).start()

    # prefetch the next tile column under this column's Q compute steps;
    # slot (bi+1) % 2 held column bi-1, fully retired by now
    @pl.when((qi == 0) & (bi + 1 < nbt))
    def _():
        dma((bi + 1) % 2, bi + 1).start()

    # wait exactly once per started copy — only the first query lane of
    # a column blocks on the DMA; later lanes reuse the resident tile
    @pl.when(qi == 0)
    def _():
        dma(bi % 2, bi).wait()

    lb1, lb = _fused_tile_compute(
        c_vmem[bi % 2], u_ref[...], l_ref[...], q_ref[...], bound_ref[...],
        w=w, n=n, p=p,
    )
    lb1_ref[...] = lb1
    lb_ref[...] = lb


@functools.partial(
    jax.jit,
    static_argnames=("w", "n", "p", "tile_b", "interpret", "depth", "grid"),
)
def lb_fused_qbatch_pallas(
    cands: jax.Array,
    upper: jax.Array,
    lower: jax.Array,
    qs: jax.Array,
    bounds: jax.Array,
    w: int,
    n: int,
    p=1,
    tile_b: int = 8,
    interpret: bool = True,
    depth: int = 1,
    grid: str = "qb",
):
    """Fused two-pass bound over schedule (tile_b, depth, grid).

    cands (B, n); envelopes + queries (Q, n); bounds (Q, 1) powered
    pruning bounds -> (lb1 (Q, B), lb (Q, B)) where ``lb`` holds the full
    LB_Improved on lanes with ``lb1 < bound`` and lb1 elsewhere.
    B % tile_b == 0.  All schedules are bit-identical (see module
    docstring); pick via the tune table.
    """
    b = cands.shape[0]
    nq = upper.shape[0]
    if b % tile_b:
        raise ValueError(f"batch {b} not a multiple of tile_b {tile_b}")
    nbt = b // tile_b
    # per-lane rows ride a unit axis ((Q, 1, n) and (Q, 1, 1) in,
    # (Q, B, 1) out) so every block's last two dims are the array's own
    # or (8, 128)-aligned, as the TPU lowering requires
    out_shape = [
        jax.ShapeDtypeStruct((nq, b, 1), cands.dtype),
        jax.ShapeDtypeStruct((nq, b, 1), cands.dtype),
    ]
    lane_spec = (
        (lambda qi, bi: (qi, 0, 0))
        if grid == "qb"
        else (lambda bi, qi: (qi, 0, 0))
    )
    out_map = (
        (lambda qi, bi: (qi, bi, 0))
        if grid == "qb"
        else (lambda bi, qi: (qi, bi, 0))
    )
    lane_specs = [
        pl.BlockSpec((None, 1, n), lane_spec),
        pl.BlockSpec((None, 1, n), lane_spec),
        pl.BlockSpec((None, 1, n), lane_spec),
        pl.BlockSpec((None, 1, 1), lane_spec),
    ]
    out_specs = [
        pl.BlockSpec((None, tile_b, 1), out_map),
        pl.BlockSpec((None, tile_b, 1), out_map),
    ]
    lanes = (upper[:, None, :], lower[:, None, :], qs[:, None, :],
             bounds[:, :, None])
    pall_grid = (nq, nbt) if grid == "qb" else (nbt, nq)

    if depth == 1:
        cand_spec = pl.BlockSpec(
            (tile_b, n),
            (lambda qi, bi: (bi, 0)) if grid == "qb" else (lambda bi, qi: (bi, 0)),
        )
        kern = functools.partial(_lb_fused_kernel, w=w, n=n, p=p)
        lb1, lb = pl.pallas_call(
            kern,
            grid=pall_grid,
            in_specs=[cand_spec, *lane_specs],
            out_specs=out_specs,
            out_shape=out_shape,
            interpret=interpret,
        )(cands, *lanes)
        return lb1[:, :, 0], lb[:, :, 0]

    # depth == 2: candidates stay unblocked (compiler-chosen memory,
    # HBM on TPU); the kernel stages tiles into a two-slot VMEM buffer
    # with explicit async copies so copy t+1 overlaps compute t.
    body = _lb_fused_db_qb_kernel if grid == "qb" else _lb_fused_db_bq_kernel
    kern = functools.partial(body, w=w, n=n, p=p, tile_b=tile_b)
    lb1, lb = pl.pallas_call(
        kern,
        grid=pall_grid,
        in_specs=[pl.BlockSpec(memory_space=pl.ANY), *lane_specs],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((2, tile_b, n), cands.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        interpret=interpret,
    )(cands, *lanes)
    return lb1[:, :, 0], lb[:, :, 0]
