"""Pallas TPU kernel: fused LB_Improved second pass.

Given the projection H(c, q) (from the lb_keogh kernel) this computes,
entirely in VMEM, the paper's Corollary 4 second term:

    U(H), L(H)  — vHGW sliding extrema of the projection
    lb2         = sum_i |q_i - clip(q_i, L(H)_i, U(H)_i)|^p

Fusing the envelope with the accumulation means H streams through VMEM
once and only a scalar per candidate returns to HBM — this is the pass
the two-pass idea adds, so it must not add a second HBM sweep.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import cummax_doubling, cummin_doubling


def _lb2_kernel(hmax_ref, hmin_ref, q_ref, lb_ref, *, w: int, n: int, p):
    win = 2 * w + 1
    hmax = hmax_ref[...]  # (tile_b, nblocks*win), -BIG padded
    hmin = hmin_ref[...]  # (tile_b, nblocks*win), +BIG padded
    q = q_ref[...]  # (1, n)
    tile_b = hmax.shape[0]
    nblocks = hmax.shape[1] // win

    bmax = hmax.reshape(tile_b * nblocks, win)
    bmin = hmin.reshape(tile_b * nblocks, win)
    pref_max = cummax_doubling(bmax, axis=1).reshape(tile_b, nblocks * win)
    suff_max = cummax_doubling(bmax[:, ::-1], axis=1)[:, ::-1].reshape(
        tile_b, nblocks * win
    )
    pref_min = cummin_doubling(bmin, axis=1).reshape(tile_b, nblocks * win)
    suff_min = cummin_doubling(bmin[:, ::-1], axis=1)[:, ::-1].reshape(
        tile_b, nblocks * win
    )
    upper = jnp.maximum(suff_max[:, :n], pref_max[:, win - 1 : win - 1 + n])
    lower = jnp.minimum(suff_min[:, :n], pref_min[:, win - 1 : win - 1 + n])

    over = jnp.maximum(q - upper, 0.0)
    under = jnp.maximum(lower - q, 0.0)
    d = over + under
    cost = d if p == 1 else d * d if p == 2 else d**p
    lb_ref[...] = jnp.sum(cost, axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("w", "n", "p", "tile_b", "interpret"))
def lb_improved_pass2_pallas(
    hpad_max: jax.Array,
    hpad_min: jax.Array,
    q: jax.Array,
    w: int,
    n: int,
    p=1,
    tile_b: int = 8,
    interpret: bool = True,
):
    """Sentinel-padded projections (B, nblocks*(2w+1)) + query (n,) -> lb2 (B,)."""
    b, total = hpad_max.shape
    win = 2 * w + 1
    if total % win or b % tile_b:
        raise ValueError((total, win, b, tile_b))
    kern = functools.partial(_lb2_kernel, w=w, n=n, p=p)
    out = pl.pallas_call(
        kern,
        grid=(b // tile_b,),
        in_specs=[
            pl.BlockSpec((tile_b, total), lambda i: (i, 0)),
            pl.BlockSpec((tile_b, total), lambda i: (i, 0)),
            pl.BlockSpec((1, n), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((tile_b, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, 1), hpad_max.dtype),
        interpret=interpret,
    )(hpad_max, hpad_min, q[None, :])
    return out[:, 0]


def _lb2_qbatch_kernel(hmax_ref, hmin_ref, q_ref, lb_ref, *, w: int, n: int, p):
    win = 2 * w + 1
    hmax = hmax_ref[...]  # (1, tile_b, nblocks*win), -BIG padded
    hmin = hmin_ref[...]  # (1, tile_b, nblocks*win), +BIG padded
    q = q_ref[...]  # (1, n) — query lane program_id(0)
    tile_b = hmax.shape[1]
    total = hmax.shape[2]
    nblocks = total // win

    bmax = hmax.reshape(tile_b * nblocks, win)
    bmin = hmin.reshape(tile_b * nblocks, win)
    pref_max = cummax_doubling(bmax, axis=1).reshape(tile_b, total)
    suff_max = cummax_doubling(bmax[:, ::-1], axis=1)[:, ::-1].reshape(
        tile_b, total
    )
    pref_min = cummin_doubling(bmin, axis=1).reshape(tile_b, total)
    suff_min = cummin_doubling(bmin[:, ::-1], axis=1)[:, ::-1].reshape(
        tile_b, total
    )
    upper = jnp.maximum(suff_max[:, :n], pref_max[:, win - 1 : win - 1 + n])
    lower = jnp.minimum(suff_min[:, :n], pref_min[:, win - 1 : win - 1 + n])

    over = jnp.maximum(q - upper, 0.0)
    under = jnp.maximum(lower - q, 0.0)
    d = over + under
    cost = d if p == 1 else d * d if p == 2 else d**p
    lb_ref[...] = jnp.sum(cost, axis=1, keepdims=True)  # (tile_b, 1)


@functools.partial(jax.jit, static_argnames=("w", "n", "p", "tile_b", "interpret"))
def lb_improved_pass2_qbatch_pallas(
    hpad_max: jax.Array,
    hpad_min: jax.Array,
    qs: jax.Array,
    w: int,
    n: int,
    p=1,
    tile_b: int = 8,
    interpret: bool = True,
):
    """Query-major pass 2 (DESIGN.md §3.4): grid (Q, B/tile_b).

    Sentinel-padded projections (Q, B, nblocks*(2w+1)) — one projection
    per (query, candidate) pair since H(c, q) depends on the query — plus
    queries (Q, n) -> lb2 (Q, B).  The query axis is a grid dimension, so
    each lane's projections and its (1, n) query row stream through VMEM
    together and one launch serves the whole batch.  Query rows ride a
    unit axis ((Q, 1, n) in, (Q, B, 1) out) so every block's last two
    dims are the array's own or (8, 128)-aligned.
    """
    nq, b, total = hpad_max.shape
    win = 2 * w + 1
    if total % win or b % tile_b:
        raise ValueError((total, win, b, tile_b))
    kern = functools.partial(_lb2_qbatch_kernel, w=w, n=n, p=p)
    out = pl.pallas_call(
        kern,
        grid=(nq, b // tile_b),
        in_specs=[
            pl.BlockSpec((1, tile_b, total), lambda qi, bi: (qi, bi, 0)),
            pl.BlockSpec((1, tile_b, total), lambda qi, bi: (qi, bi, 0)),
            pl.BlockSpec((None, 1, n), lambda qi, bi: (qi, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, tile_b, 1), lambda qi, bi: (qi, bi, 0)),
        out_shape=jax.ShapeDtypeStruct((nq, b, 1), hpad_max.dtype),
        interpret=interpret,
    )(hpad_max, hpad_min, qs[:, None, :])
    return out[:, :, 0]
