"""Pallas TPU kernel: fused LB_Keogh — clamp-project-accumulate.

For a tile of candidates resident in VMEM this computes, in one pass over
the data (paper Algorithm 2 lines 7-12 + Algorithm 3's projection):

    over  = max(c - U, 0);  under = max(L - c, 0)
    lb    = sum_i (over + under)^p          (powered LB_Keogh)
    H     = clip(c, L, U)                   (projection, Eq. 1)

Emitting both lb and H in the same kernel is what makes the two-pass
LB_Improved cheap: pass 2 re-uses H without another sweep through HBM.
The query envelope (U, L) is broadcast to every grid step; candidates
stream through VMEM tile by tile.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _lb_keogh_kernel(c_ref, u_ref, l_ref, lb_ref, h_ref, *, p):
    c = c_ref[...]  # (tile_b, n)
    u = u_ref[...]  # (1, n)
    l = l_ref[...]
    over = jnp.maximum(c - u, 0.0)
    under = jnp.maximum(l - c, 0.0)
    d = over + under  # one side is always 0
    if p == 1:
        cost = d
    elif p == 2:
        cost = d * d
    else:
        cost = d**p
    lb_ref[...] = jnp.sum(cost, axis=1, keepdims=True)
    h_ref[...] = jnp.clip(c, l, u)


@functools.partial(jax.jit, static_argnames=("p", "tile_b", "interpret"))
def lb_keogh_pallas(
    cands: jax.Array,
    upper: jax.Array,
    lower: jax.Array,
    p=1,
    tile_b: int = 8,
    interpret: bool = True,
):
    """cands (B, n), envelope (n,) -> (lb (B,), H (B, n)); B % tile_b == 0."""
    b, n = cands.shape
    if b % tile_b:
        raise ValueError(f"batch {b} not a multiple of tile_b {tile_b}")
    grid = (b // tile_b,)
    kern = functools.partial(_lb_keogh_kernel, p=p)
    lb, h = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_b, n), lambda i: (i, 0)),
            pl.BlockSpec((1, n), lambda i: (0, 0)),
            pl.BlockSpec((1, n), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((tile_b, 1), lambda i: (i, 0)),
            pl.BlockSpec((tile_b, n), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, 1), cands.dtype),
            jax.ShapeDtypeStruct((b, n), cands.dtype),
        ],
        interpret=interpret,
    )(cands, upper[None, :], lower[None, :])
    return lb[:, 0], h


def _lb_keogh_qbatch_kernel(c_ref, u_ref, l_ref, lb_ref, h_ref, *, p):
    c = c_ref[...]  # (tile_b, n) — candidate tile, shared by all queries
    u = u_ref[...]  # (1, n) — envelope of query lane program_id(0)
    l = l_ref[...]
    over = jnp.maximum(c - u, 0.0)
    under = jnp.maximum(l - c, 0.0)
    d = over + under  # one side is always 0
    if p == 1:
        cost = d
    elif p == 2:
        cost = d * d
    else:
        cost = d**p
    lb_ref[...] = jnp.sum(cost, axis=1, keepdims=True)  # (tile_b, 1)
    h_ref[...] = jnp.clip(c, l, u)[None]  # (1, tile_b, n)


def _lb_keogh_stream_qbatch_kernel(
    seg_ref, u_ref, l_ref, lb_ref, h_ref, *, p, n, hop, tile_b
):
    """Window-lane tile built *inside* the kernel: the flat stream
    segment lives in VMEM once and each lane is a dynamic slice
    ``seg[base + r*hop : ... + n]`` — hop-strided windows overlap by
    ``n - hop`` samples, so packing them as materialized rows would
    stream ~n/hop times more HBM traffic than the segment itself."""
    bi = pl.program_id(1)
    base = bi * (tile_b * hop)
    rows = [
        seg_ref[0, pl.dslice(base + r * hop, n)] for r in range(tile_b)
    ]
    c = jnp.stack(rows, axis=0)  # (tile_b, n) window tile
    u = u_ref[...]  # (1, n) — envelope of template lane program_id(0)
    l = l_ref[...]
    over = jnp.maximum(c - u, 0.0)
    under = jnp.maximum(l - c, 0.0)
    d = over + under  # one side is always 0
    if p == 1:
        cost = d
    elif p == 2:
        cost = d * d
    else:
        cost = d**p
    lb_ref[...] = jnp.sum(cost, axis=1, keepdims=True)  # (tile_b, 1)
    h_ref[...] = jnp.clip(c, l, u)[None]  # (1, tile_b, n)


@functools.partial(
    jax.jit, static_argnames=("n", "hop", "p", "tile_b", "interpret")
)
def lb_keogh_stream_qbatch_pallas(
    segment: jax.Array,
    upper: jax.Array,
    lower: jax.Array,
    n: int,
    hop: int,
    p=1,
    tile_b: int = 8,
    interpret: bool = True,
):
    """Stream-packed LB_Keogh (DESIGN.md §3.5): grid (Q, B/tile_b).

    segment (1, L) — a flat stream slice holding B hop-strided windows
    of length n (L == (B-1)*hop + n) — and envelopes (Q, n) ->
    (lb (Q, B), H (Q, B, n)).  One launch serves every (template,
    window) pair of the block; the segment is broadcast to every grid
    step and window lanes are sliced out in VMEM, never materialized
    in HBM.  B % tile_b == 0.
    """
    length = segment.shape[1]
    b = (length - n) // hop + 1
    nq = upper.shape[0]
    if (b - 1) * hop + n != length:
        raise ValueError(f"segment length {length} != (B-1)*hop+n for B={b}")
    if b % tile_b:
        raise ValueError(f"windows {b} not a multiple of tile_b {tile_b}")
    grid = (nq, b // tile_b)
    kern = functools.partial(
        _lb_keogh_stream_qbatch_kernel, p=p, n=n, hop=hop, tile_b=tile_b
    )
    lb, h = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, length), lambda qi, bi: (0, 0)),
            pl.BlockSpec((None, 1, n), lambda qi, bi: (qi, 0, 0)),
            pl.BlockSpec((None, 1, n), lambda qi, bi: (qi, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, tile_b, 1), lambda qi, bi: (qi, bi, 0)),
            pl.BlockSpec((1, tile_b, n), lambda qi, bi: (qi, bi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nq, b, 1), segment.dtype),
            jax.ShapeDtypeStruct((nq, b, n), segment.dtype),
        ],
        interpret=interpret,
    )(segment, upper[:, None, :], lower[:, None, :])
    return lb[:, :, 0], h


@functools.partial(jax.jit, static_argnames=("p", "tile_b", "interpret"))
def lb_keogh_qbatch_pallas(
    cands: jax.Array,
    upper: jax.Array,
    lower: jax.Array,
    p=1,
    tile_b: int = 8,
    interpret: bool = True,
):
    """Query-major LB_Keogh (DESIGN.md §3.4): grid (Q, B/tile_b).

    cands (B, n), envelopes (Q, n) -> (lb (Q, B), H (Q, B, n)).
    The query axis is a second grid dimension: each candidate tile is
    streamed into VMEM once per query lane while the (1, n) envelope row
    for that lane is broadcast across the candidate grid axis, so one
    launch serves the whole query batch.  Per-lane rows ride a unit
    axis ((Q, 1, n) in, (Q, B, 1) out) so every block's last two dims
    are the array's own or (8, 128)-aligned, as the TPU lowering
    requires.  B % tile_b == 0.
    """
    b, n = cands.shape
    nq = upper.shape[0]
    if b % tile_b:
        raise ValueError(f"batch {b} not a multiple of tile_b {tile_b}")
    grid = (nq, b // tile_b)
    kern = functools.partial(_lb_keogh_qbatch_kernel, p=p)
    lb, h = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_b, n), lambda qi, bi: (bi, 0)),
            pl.BlockSpec((None, 1, n), lambda qi, bi: (qi, 0, 0)),
            pl.BlockSpec((None, 1, n), lambda qi, bi: (qi, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, tile_b, 1), lambda qi, bi: (qi, bi, 0)),
            pl.BlockSpec((1, tile_b, n), lambda qi, bi: (qi, bi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nq, b, 1), cands.dtype),
            jax.ShapeDtypeStruct((nq, b, n), cands.dtype),
        ],
        interpret=interpret,
    )(cands, upper[:, None, :], lower[:, None, :])
    return lb[:, :, 0], h
