"""Pallas TPU kernel: banded DTW_p dynamic program, early-abandoning.

One grid step computes DTW_p(q, c) for a single candidate.  The DP runs
row-by-row; the loop-carried band row (width 2w+1) lives in VMEM/VREGs
for the whole computation, so HBM traffic is exactly the two input
series (plus one bound scalar).  The within-row (min,+) recurrence is
solved in closed form with one cumsum + one cummin (Hillis-Steele
doubling — log2(W) vector steps), the same restructuring as
repro.core.dtw.dtw_banded (DESIGN.md §3).

The row loop is a ``lax.while_loop`` threaded with the lane's powered
pruning bound (paper §3's early-abandoning optimisation, the device
twin of ``repro.core.dtw.dtw_banded_early``): row minima of the (min,+)
DP are non-decreasing, so once every band cell meets or exceeds the
bound the final distance provably does too and the remaining rows are
skipped.  Abandoned lanes return the running band min — a value
>= bound, which the cascade's top-k can never admit past the bound it
supplied.  A BIG bound degrades to the exact full-row DP.

Layout notes:
* the candidate arrives pre-padded with PAD_VALUE sentinels on both sides
  (length n + 2w) so each row's cost slice ``ypad[i : i + 2w + 1]`` is a
  contiguous dynamic slice — no gathers;
* validity of a band cell is derived from a static iota against the
  dynamic row index, all (1, W)-shaped (Mosaic wants >= 2-D);
* supports p in {1, 2} (the cascade's fast path); other p values use the
  pure-jnp path in repro.core.
* ``depth=2`` (tune-table resolved) double-buffers the candidate rows:
  lane i+1's padded row is DMA'd into the spare VMEM slot while lane i's
  row loop runs, so the DP never stalls on the HBM fetch.  Same math,
  same outputs — a schedule knob only.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import BIG, cummin_doubling, cumsum_doubling


def _dtw_lane(q_ref, y_ref, bound, out_ref, *, n: int, w: int, p):
    """The band DP for one candidate lane; ``y_ref`` is the lane's
    padded (1, n + 2w) row, already resident in VMEM.  Shared by both
    schedules — the bit-identity argument in code form."""
    width = 2 * w + 1
    ks = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)  # band offset k

    prev0 = jnp.where(ks == w, 0.0, BIG).astype(jnp.float32)  # origin

    def row(state):
        i, prev = state
        yrow = y_ref[:, pl.ds(i, width)]
        qi = q_ref[0, i]
        diff = jnp.abs(qi - yrow)
        cost = diff if p == 1 else diff * diff
        j = i + ks - w  # column index of each band cell
        valid = (j >= 0) & (j < n)
        cost_sum = jnp.where(valid, cost, 0.0)

        up = jnp.concatenate(
            [prev[:, 1:], jnp.full((1, 1), BIG, jnp.float32)], axis=1
        )
        b = jnp.minimum(up, prev)
        s = cumsum_doubling(cost_sum, axis=1)
        t = jnp.where(valid, b + cost_sum - s, BIG)
        new = jnp.minimum(s + cummin_doubling(t, axis=1), BIG)
        return i + 1, jnp.where(valid, new, BIG)

    def cond(state):
        i, prev = state
        # row minima are non-decreasing: once the whole band clears the
        # bound, the final cell will too — the remaining rows are skipped
        return (i < n) & (jnp.min(prev) < bound)

    i, last = jax.lax.while_loop(cond, row, (jnp.int32(0), prev0))
    # finished: exact powered DTW; abandoned: a valid lower bound >= bound
    # (cell k = w picked by a masked max: exact, and a (1, 1) value —
    # the TPU stores vectors to VMEM, never scalars)
    at_w = jnp.max(jnp.where(ks == w, last, -BIG), axis=1, keepdims=True)
    low = jnp.min(last, axis=1, keepdims=True)
    out_ref[...] = jnp.where(i == n, at_w, low)


def _dtw_kernel(q_ref, ypad_ref, bound_ref, out_ref, *, n: int, w: int, p):
    """depth=1: the padded row arrives via the BlockSpec pipeline."""
    _dtw_lane(q_ref, ypad_ref, bound_ref[0, 0], out_ref, n=n, w=w, p=p)


def _dtw_db_kernel(
    q_ref, ypad_hbm, bound_ref, out_ref, y_vmem, sem, *, n: int, w: int, p
):
    """depth=2: two-slot staging — lane i+1's padded row is copied while
    lane i's row loop runs, so the DP never waits on HBM."""
    i = pl.program_id(0)
    nb = pl.num_programs(0)

    def dma(slot, lane):
        return pltpu.make_async_copy(
            ypad_hbm.at[lane], y_vmem.at[slot], sem.at[slot]
        )

    @pl.when(i == 0)
    def _():
        dma(0, 0).start()

    # slot (i+1) % 2 held lane i-1, whose DP has retired (sequential grid)
    @pl.when(i + 1 < nb)
    def _():
        dma((i + 1) % 2, i + 1).start()

    dma(i % 2, i).wait()
    _dtw_lane(
        q_ref, y_vmem.at[i % 2], bound_ref[0, 0], out_ref, n=n, w=w, p=p
    )


@functools.partial(
    jax.jit, static_argnames=("n", "w", "p", "interpret", "depth")
)
def dtw_banded_pallas(
    q: jax.Array,
    cands_pad: jax.Array,
    bounds: jax.Array,
    n: int,
    w: int,
    p=1,
    interpret: bool = True,
    depth: int = 1,
):
    """q (1, n); cands_pad (B, n + 2w) sentinel-padded; bounds (B, 1)
    per-lane powered abandon thresholds -> powered DTW (B,).  ``depth``
    selects single-buffered BlockSpec staging (1) or the double-buffered
    row prefetch (2) — outputs are bit-identical either way."""
    b = cands_pad.shape[0]
    # per-lane rows ride a unit axis ((B, 1, n + 2w) and (B, 1, 1)) so
    # every block's last two dims are the array's own, as the TPU
    # lowering requires
    cands_pad = cands_pad[:, None, :]
    bounds = bounds[:, :, None]
    q_spec = pl.BlockSpec((1, n), lambda i: (0, 0))
    bound_spec = pl.BlockSpec((None, 1, 1), lambda i: (i, 0, 0))
    out_spec = pl.BlockSpec((None, 1, 1), lambda i: (i, 0, 0))
    out_shape = jax.ShapeDtypeStruct((b, 1, 1), jnp.float32)
    if depth == 1:
        kern = functools.partial(_dtw_kernel, n=n, w=w, p=p)
        out = pl.pallas_call(
            kern,
            grid=(b,),
            in_specs=[
                q_spec,
                pl.BlockSpec((None, 1, n + 2 * w), lambda i: (i, 0, 0)),
                bound_spec,
            ],
            out_specs=out_spec,
            out_shape=out_shape,
            interpret=interpret,
        )(q, cands_pad, bounds)
        return out[:, 0, 0]
    kern = functools.partial(_dtw_db_kernel, n=n, w=w, p=p)
    out = pl.pallas_call(
        kern,
        grid=(b,),
        in_specs=[q_spec, pl.BlockSpec(memory_space=pl.ANY), bound_spec],
        out_specs=out_spec,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((2, 1, n + 2 * w), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        interpret=interpret,
    )(q, cands_pad, bounds)
    return out[:, 0, 0]
