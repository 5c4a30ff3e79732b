"""Pallas TPU kernel: LB_Kim — constant-time first/last/extremum bound.

For a candidate tile resident in VMEM this computes, per lane, the
four O(1) feature distances of Kim's bound (see ``core/lb.py`` for the
soundness argument):

    d_first = cost(|c_0     - q_0    |)      (path start cell)
    d_last  = cost(|c_{n-1} - q_{n-1}|)      (path end cell)
    d_max   = cost(|max c   - max q  |)      (some path cell)
    d_min   = cost(|min c   - min q  |)

    p finite:  lb = max(d_first + d_last, max(d_max, d_min))
    p = inf:   lb = max(d_first, d_last, d_max, d_min)

First and last are distinct path cells (n >= 2) so their powered costs
add; the extremum cells may alias the endpoints, so they only combine
by max.  The tile's extrema are row reductions over data already in
VMEM — the whole stage is one sweep with a four-scalar output per lane,
which is why LB_Kim sits *before* the envelope stages in the cascade:
it needs no envelopes at all.

The qbatch form carries an entry-mask row per query lane (the cascade's
``mask0``): lanes masked off emit ``BIG`` so they stay dead downstream
regardless of their data (pad lanes of a ragged final block are masked
the same way).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import BIG


def _kim_cost(d, p):
    if p == 1 or p == jnp.inf:
        return d
    if p == 2:
        return d * d
    return d**p


def _lane(x, j: int):
    """Column ``j`` of a 2-D tile as a (rows, 1) value.  A masked max
    picks the element exactly; a one-lane slice at an unaligned offset
    has no TPU vector layout."""
    lanes = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.max(jnp.where(lanes == j, x, -BIG), axis=1, keepdims=True)


def _lb_kim_qbatch_kernel(c_ref, q_ref, mask_ref, lb_ref, *, p):
    c = c_ref[...]  # (tile_b, n) — candidate tile, shared by all queries
    q = q_ref[...]  # (1, n) — query lane program_id(0)
    mask = mask_ref[...]  # (tile_b, 1) entry mask, 0.0 = dead lane
    n = c.shape[1]
    d_first = _kim_cost(jnp.abs(_lane(c, 0) - _lane(q, 0)), p)
    d_last = _kim_cost(jnp.abs(_lane(c, n - 1) - _lane(q, n - 1)), p)
    d_max = _kim_cost(jnp.abs(jnp.max(c, axis=1, keepdims=True) - jnp.max(q)), p)
    d_min = _kim_cost(jnp.abs(jnp.min(c, axis=1, keepdims=True) - jnp.min(q)), p)
    if p == jnp.inf:
        lb = jnp.maximum(
            jnp.maximum(d_first, d_last), jnp.maximum(d_max, d_min)
        )
    else:
        lb = jnp.maximum(d_first + d_last, jnp.maximum(d_max, d_min))
    lb_ref[...] = jnp.where(mask > 0, lb, BIG)  # (tile_b, 1)


@functools.partial(jax.jit, static_argnames=("p", "tile_b", "interpret"))
def lb_kim_qbatch_pallas(
    cands: jax.Array,
    qs: jax.Array,
    mask: jax.Array,
    p=1,
    tile_b: int = 8,
    interpret: bool = True,
):
    """Query-major LB_Kim (DESIGN.md §3.4): grid (Q, B/tile_b).

    cands (B, n), queries (Q, n), mask (Q, B) float entry mask ->
    lb (Q, B): powered LB_Kim where ``mask > 0``, BIG elsewhere.
    Each candidate tile streams into VMEM once per query lane; the
    (1, n) query row and its (tile_b, 1) mask column broadcast across
    the candidate grid axis.  Per-lane rows ride a unit axis — (Q, 1, n)
    in, (Q, B, 1) out — so every block's last two dims are either the
    array's own or (8, 128)-aligned, as the TPU lowering requires.
    B % tile_b == 0.
    """
    b, n = cands.shape
    nq = qs.shape[0]
    if b % tile_b:
        raise ValueError(f"batch {b} not a multiple of tile_b {tile_b}")
    grid = (nq, b // tile_b)
    kern = functools.partial(_lb_kim_qbatch_kernel, p=p)
    lb = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_b, n), lambda qi, bi: (bi, 0)),
            pl.BlockSpec((None, 1, n), lambda qi, bi: (qi, 0, 0)),
            pl.BlockSpec((None, tile_b, 1), lambda qi, bi: (qi, bi, 0)),
        ],
        out_specs=pl.BlockSpec((None, tile_b, 1), lambda qi, bi: (qi, bi, 0)),
        out_shape=jax.ShapeDtypeStruct((nq, b, 1), cands.dtype),
        interpret=interpret,
    )(cands, qs[:, None, :], mask[:, :, None])
    return lb[:, :, 0]
