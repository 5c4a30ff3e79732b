"""Banded Dynamic Time Warping (DTW_p) — the paper's Section 4.

The paper computes DTW_p(x, y): the minimum, over monotonic warping paths
Gamma constrained to the Sakoe-Chiba band |i - j| <= w, of the l_p norm of
the aligned differences.  The textbook DP is O(n * (2w+1)) with a
loop-carried dependency inside each row; here we restructure it for SIMD /
TPU execution (see DESIGN.md section 3):

* ``dtw_banded``   — row-wise DP where the within-row (min,+) recurrence is
  solved in closed form: the inclusive cost prefix sums S of every row are
  one ``cumsum`` over the whole (n, 2w+1) band before the row loop, and
  each row step is one ``cummin`` by doubling (ceil(log2(2w+1)) shifted
  minimums).  n sequential steps, each a few dense vector ops of width
  2w+1.  The TPU lowers ``cumsum``/``cummin`` to reduce-windows; with the
  band on its 128 lanes those do O(band x 128) work per cell, and inside
  the loop they took 6.0 of 6.92 us per 16-pair row at w = 100 on a v5e.
  Vmapped over 128 pairs or more the pairs take the lanes, and the cumsum
  stays in the loop, where it is then cheap (``_by_batch``).  One
  ``_row_step`` serves this, the early-abandoning DP and their
  multivariate twins (repro.mv.dtw).
* ``dtw_banded_diag`` — anti-diagonal wavefront (2n-1 steps); handles all
  p including p = inf with purely elementwise ops.  This is the layout the
  Pallas kernel (repro.kernels.dtw) mirrors.
* ``dtw_reference`` — O(n^2) numpy oracle used by the test-suite and the
  kernel ref.py files.

All series are equal-length 1-D float arrays (paper convention).  Banded
values are stored in "band coordinates": for row i, band index
k in [0, 2w] corresponds to column j = i + (k - w).
"""

from __future__ import annotations

import functools
from typing import Union

import jax
import jax.numpy as jnp
import numpy as np

# Large-but-finite sentinel: +inf poisons (min,+) prefix sums with NaNs
# (inf - inf); 1e30 survives fp32 cumsums over any band width we use.
BIG: float = 1.0e30

PNorm = Union[int, float]


def _check_pair(x: jax.Array, y: jax.Array) -> int:
    if x.ndim != 1 or y.ndim != 1:
        raise ValueError(f"dtw expects 1-D series, got {x.shape} / {y.shape}")
    if x.shape[0] != y.shape[0]:
        raise ValueError(
            f"paper's DTW bounds assume equal lengths, got {x.shape[0]} != {y.shape[0]}"
        )
    return x.shape[0]


def elem_cost(diff: jax.Array, p: PNorm) -> jax.Array:
    """|diff|^p for finite p, |diff| for p = inf (combined with max later)."""
    if p == jnp.inf:
        return jnp.abs(diff)
    if p == 1:
        return jnp.abs(diff)
    if p == 2:
        return diff * diff
    return jnp.abs(diff) ** p


def finish_cost(acc: jax.Array, p: PNorm) -> jax.Array:
    """Map the accumulated powered cost back to the l_p distance."""
    if p == jnp.inf or p == 1:
        return acc
    if p == 2:
        return jnp.sqrt(acc)
    return acc ** (1.0 / p)


def _band_costs(x: jax.Array, y: jax.Array, w: int, p: PNorm) -> jax.Array:
    """(n, 2w+1) matrix of elementwise costs in band coordinates.

    entry [i, k] = cost(x[i], y[i + k - w]); out-of-range columns get BIG.
    Built with a gather so it vectorises (and vmaps) cleanly.
    """
    n = x.shape[0]
    width = 2 * w + 1
    rows = jnp.arange(n)[:, None]  # i
    cols = rows + (jnp.arange(width)[None, :] - w)  # j
    valid = (cols >= 0) & (cols < n)
    y_g = y[jnp.clip(cols, 0, n - 1)]
    c = elem_cost(x[:, None] - y_g, p)
    return jnp.where(valid, c, BIG), valid


def cummin_doubling(x: jax.Array, axis: int = -1) -> jax.Array:
    """Inclusive prefix min by Hillis-Steele doubling over a static shape:
    ceil(log2(W)) shifted ``minimum``s, the shifted-in front padded with
    BIG.  A min never rounds, so on values <= BIG this equals
    ``lax.cummin`` bit for bit."""
    n = x.shape[axis]
    shift = 1
    while shift < n:
        pad = [(0, 0)] * x.ndim
        pad[axis] = (shift, 0)
        sl = [slice(None)] * x.ndim
        sl[axis] = slice(0, n)
        x = jnp.minimum(x, jnp.pad(x, pad, constant_values=BIG)[tuple(sl)])
        shift *= 2
    return x


def _row_step(
    prev: jax.Array,
    cost_sum_row: jax.Array,
    valid_row: jax.Array,
    s_row: jax.Array | None = None,
) -> jax.Array:
    """One band row of the (min,+) DP from the row before it.

    ``s_row`` is the inclusive cumsum of ``cost_sum_row``, taken for all
    rows before the row loop where that pays (``_by_batch``); see
    ``dtw_banded`` for the closed form.
    """
    if s_row is None:
        s_row = jnp.cumsum(cost_sum_row)
    up = jnp.concatenate([prev[1:], jnp.array([BIG], prev.dtype)])
    b = jnp.minimum(up, prev)
    t = jnp.where(valid_row, b + cost_sum_row - s_row, BIG)
    # clip to keep BIG from overflowing after repeated additions
    row = jnp.minimum(s_row + cummin_doubling(t), BIG)
    return jnp.where(valid_row, row, BIG)


def _row_inputs(costs: jax.Array, valid: jax.Array, w: int, hoist: bool):
    """The row loop's inputs: the costs with out-of-band cells zeroed
    (they enter S only), each row's cost prefix sums S if ``hoist``, and
    the virtual row -1 holding the origin D[-1, -1] = 0 at k = w.

    The origin feeds row 0 via "diag" only: row 0, cell k reads prev[k]
    (diag -> D[-1, j-1], only j = 0 i.e. k = w is the origin) and
    prev[k+1] (up -> D[-1, j], never valid).  "Up" from the origin would
    be prev[k+1] = 0 at k = w-1, i.e. column j = -1, an invalid cell, so
    it is harmless.
    """
    costs_sum = jnp.where(valid, costs, 0.0)
    s = jnp.cumsum(costs_sum, axis=-1) if hoist else None
    prev0 = jnp.full((2 * w + 1,), BIG, costs.dtype).at[w].set(0.0)
    return costs_sum, s, prev0


#: vmapped pairs from which the row loop keeps each row's cumsum inside
#: the loop (``_by_batch``)
WIDE_BATCH = 128


def _by_batch(fn):
    """``fn(*arrays, hoist=...)``, hoisting S out of the row loop unless
    it runs vmapped over ``WIDE_BATCH`` pairs or more.

    The TPU lowers ``cumsum`` to a reduce-window.  With few pairs XLA
    lays the band on the 128 lanes, where that costs O(band x 128) per
    cell, so one whole-band cumsum before the loop saves most of the DP
    (v5e, n = 1000, w = 100: 16 pairs 4.44 ms with a cumsum per row, 1.78
    hoisted).  From 128 pairs on it lays the pairs on the lanes: a cumsum
    per row is then cheap, and the hoisted one costs more than it saves
    and doubles the DP's memory (128 pairs: 1.54 ms, 7.43 hoisted).
    Both give the per-row form's values: bit for bit on the CPU, and on
    the v5e at every batch size measured but 64 pairs (1.3e-6 apart).
    """

    @jax.custom_batching.custom_vmap
    def call(*args):
        return fn(*args, hoist=True)

    @call.def_vmap
    def _batched(axis_size, in_batched, *args):
        one = functools.partial(fn, hoist=axis_size < WIDE_BATCH)
        axes = [0 if b else None for b in in_batched]
        return jax.vmap(one, in_axes=axes)(*args), True

    return call


def _band_dp(costs, valid, *, w: int, hoist: bool):
    costs_sum, s, prev0 = _row_inputs(costs, valid, w, hoist)

    def step(prev, rows):
        return _row_step(prev, *rows), None

    last, _ = jax.lax.scan(step, prev0, (costs_sum, valid, s))
    return last[w]  # cell (n-1, j=n-1) -> k = w


def _band_dp_early(costs, valid, bound, *, w: int, hoist: bool):
    costs_sum, s, prev0 = _row_inputs(costs, valid, w, hoist)
    n = costs.shape[0]
    ks = jnp.arange(2 * w + 1)

    def cond(state):
        i, prev = state
        return (i < n) & (jnp.min(prev) < bound)

    def step(state):
        i, prev = state
        # a row's validity from its index, not a per-lane gather of `valid`
        j = i + ks - w
        s_row = None if s is None else s[i]
        return i + 1, _row_step(prev, costs_sum[i], (j >= 0) & (j < n), s_row)

    i, last = jax.lax.while_loop(cond, step, (jnp.int32(0), prev0))
    # abandoned: every cell >= bound, min(last) is a valid lower bound
    return jnp.where(i == n, last[w], jnp.min(last))


def band_dp(costs: jax.Array, valid: jax.Array, w: int) -> jax.Array:
    """Powered DTW from the (n, 2w+1) band costs and their validity
    mask: all n rows, a scan."""
    return _by_batch(functools.partial(_band_dp, w=w))(costs, valid)


def band_dp_early(
    costs: jax.Array, valid: jax.Array, w: int, bound: jax.Array
) -> jax.Array:
    """``band_dp`` that stops once every band cell exceeds ``bound``
    (powered): the exact value if it ran to the end, else the band min,
    a lower bound >= ``bound``."""
    return _by_batch(functools.partial(_band_dp_early, w=w))(costs, valid, bound)


@functools.partial(jax.jit, static_argnames=("w", "p", "powered"))
def dtw_banded(
    x: jax.Array, y: jax.Array, w: int, p: PNorm = 1, powered: bool = False
) -> jax.Array:
    """DTW_p(x, y) with Sakoe-Chiba band half-width ``w`` (finite p).

    Row-scan formulation.  Within a row the recurrence

        row[k] = cost[k] + min(b[k], row[k-1]),
        b[k]   = min(prev[k+1], prev[k])          # "up" / "diag"

    is a first-order (min,+) recurrence whose closed form is

        row[k] = S[k] + cummin(b + cost - S)[k],  S = inclusive cumsum(cost)

    so no sequential inner loop.  S depends on the costs alone, so one
    ``cumsum`` over the whole band matrix gives every row's S before the
    row loop starts (unless vmapped over ``WIDE_BATCH`` pairs or more,
    see ``_by_batch``); in the loop the cummin is a doubling scan of
    ceil(log2(2w+1)) shifted minimums (8 at w = 100).  Both keep the bits
    of a per-row ``cumsum`` + ``lax.cummin``, and keep the TPU's
    reduce-window lowering of those two (O(band x 128) work per cell
    with the band on its lanes) out of the n serial steps.
    Out-of-band cells contribute 0 to S (so sums stay well-scaled) and BIG
    to the cummin argument (so no path can enter there); see dtw.py module
    docstring for why the resulting garbage in the invalid suffix is never
    read by a valid cell.
    """
    if p == jnp.inf:
        raise ValueError("use dtw_banded_diag for p = inf")
    n = _check_pair(x, y)
    w = int(min(w, n - 1))
    out = band_dp(*_band_costs(x, y, w, p), w)
    return out if powered else finish_cost(out, p)


@functools.partial(jax.jit, static_argnames=("w", "p", "powered"))
def dtw_banded_diag(
    x: jax.Array, y: jax.Array, w: int, p: PNorm = 1, powered: bool = False
) -> jax.Array:
    """DTW_p via anti-diagonal wavefront; supports every p including inf.

    Cells on diagonal d = i + j depend only on diagonals d-1 and d-2, so a
    whole diagonal updates in one vector op.  We index a diagonal by
    e = (i - j + w) / 1 restricted to the band, storing a fixed-width
    vector of 2w+1 slots (slot e <-> i - j = e - w).  Moving from diagonal
    d to d+1, a cell (i,j) on d+1 reads:
        up   (i-1, j)   : slot e-1 of diag d
        left (i, j-1)   : slot e+1 of diag d
        diag (i-1, j-1) : slot e   of diag d-1
    """
    n = _check_pair(x, y)
    w = int(min(w, n - 1))
    width = 2 * w + 1
    slots = jnp.arange(width)  # e = i - j + w

    def diag_cells(d):
        # on diagonal d: i = (d + (e - w)) / 2 must be integer & in range
        i2 = d + (slots - w)
        i = i2 // 2
        j = d - i
        ok = (i2 % 2 == 0) & (i >= 0) & (i < n) & (j >= 0) & (j < n)
        return i, j, ok

    xpad = x
    ypad = y

    def step(carry, d):
        dm1, dm2 = carry
        i, j, ok = diag_cells(d)
        c = elem_cost(xpad[jnp.clip(i, 0, n - 1)] - ypad[jnp.clip(j, 0, n - 1)], p)
        up = jnp.concatenate([jnp.array([BIG], dm1.dtype), dm1[:-1]])
        left = jnp.concatenate([dm1[1:], jnp.array([BIG], dm1.dtype)])
        diag = dm2
        best = jnp.minimum(jnp.minimum(up, left), diag)
        # origin: cell (0,0) on d=0 has no predecessor
        best = jnp.where((d == 0) & (slots == w), 0.0, best)
        if p == jnp.inf:
            val = jnp.maximum(c, best)
        else:
            val = c + jnp.minimum(best, BIG)
        val = jnp.where(ok, jnp.minimum(val, BIG), BIG)
        return (val, dm1), None

    init = (jnp.full((width,), BIG, x.dtype), jnp.full((width,), BIG, x.dtype))
    (last, _), _ = jax.lax.scan(step, init, jnp.arange(2 * n - 1))
    out = last[w]
    return out if powered else finish_cost(out, p)


def dtw_batch(
    query: jax.Array,
    candidates: jax.Array,
    w: int,
    p: PNorm = 1,
    powered: bool = False,
) -> jax.Array:
    """vmapped DTW: one query (n,) against candidates (B, n) -> (B,)."""
    fn = dtw_banded if p != jnp.inf else dtw_banded_diag
    return jax.vmap(lambda c: fn(query, c, w, p, powered))(candidates)


def dtw_qbatch(
    queries: jax.Array,
    candidates: jax.Array,
    w: int,
    p: PNorm = 1,
    powered: bool = False,
) -> jax.Array:
    """DTW over every pair: queries (Q, n) x candidates (B, n) -> (Q, B).

    The query-major cascade (DESIGN.md §3.4) runs the banded DP for every
    (query, candidate) pair of a block in one dispatch; each lane executes
    the same op sequence as ``dtw_batch``, so values are bit-identical to
    the per-query path.  The pairs ride one flat vmap axis rather than a
    vmap of vmaps: on a TPU v5e the nested (4, 32)-pair form took 28 ms
    per n=1000 DP and the flat 128-pair form 2.5 ms, bit for bit equal.
    """
    fn = dtw_banded if p != jnp.inf else dtw_banded_diag
    nq, nb = queries.shape[0], candidates.shape[0]
    vals = jax.vmap(lambda q, c: fn(q, c, w, p, powered))(
        jnp.repeat(queries, nb, axis=0), jnp.tile(candidates, (nq, 1))
    )
    return vals.reshape(nq, nb)


@functools.partial(jax.jit, static_argnames=("w", "p"))
def dtw_banded_early(
    x: jax.Array, y: jax.Array, w: int, bound: jax.Array, p: PNorm = 1
) -> jax.Array:
    """Early-abandoning banded DTW (paper §3's optimisation; used by the
    author's own lbimproved library): the row DP stops as soon as every
    band cell already exceeds ``bound`` (powered), since row minima are
    non-decreasing.  Returns the powered DTW, or >= bound if abandoned.

    Uses lax.while_loop, so the saved rows are real skipped work — used
    by the host cascade where the running best-so-far supplies ``bound``.
    """
    if p == jnp.inf:
        raise ValueError("early abandon implemented for finite p")
    n = _check_pair(x, y)
    w = int(min(w, n - 1))
    return band_dp_early(*_band_costs(x, y, w, p), w, bound)


def dtw_reference(x, y, w: int, p: PNorm = 1) -> float:
    """O(n^2) numpy oracle (tests + kernel ref).  Matches the paper's
    recursive definition exactly, including the w >= n unconstrained case."""
    x = np.asarray(x, dtype=np.float64)
    y = np.asarray(y, dtype=np.float64)
    n, m = len(x), len(y)
    w_eff = max(int(w), abs(n - m))
    D = np.full((n + 1, m + 1), np.inf)
    D[0, 0] = 0.0
    for i in range(1, n + 1):
        lo = max(1, i - w_eff)
        hi = min(m, i + w_eff)
        for j in range(lo, hi + 1):
            d = abs(x[i - 1] - y[j - 1])
            c = d if p in (1, np.inf) else d**p
            best = min(D[i - 1, j], D[i, j - 1], D[i - 1, j - 1])
            D[i, j] = max(c, best) if p == np.inf else c + best
    q = D[n, m]
    if p in (1, np.inf):
        return float(q)
    return float(q ** (1.0 / p))
