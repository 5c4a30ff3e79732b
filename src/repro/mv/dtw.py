"""Dependent multivariate banded DTW on channel-major flattened rows.

Dependent DTW (the TC-DTW / mocap-literature convention): **one** warping
path shared by all d channels, local cell cost

    cost(i, j) = sum_ch |x_ch[i] - y_ch[j]|^p     (finite p)
               = max_ch |x_ch[i] - y_ch[j]|       (p = inf)

combined along the path by + (max at inf).  This is exactly the l_p norm
over all aligned (cell, channel) *scalar* pairs, so every univariate
result that only uses the norm structure — the envelope sandwich
(paper Cor. 3/4), Theorem 1's banded triangle inequality with constant
``min(2w+1, n)^(1/p)`` — carries over with n = per-channel length
(DESIGN.md §3.12).  At d = 1 it *is* univariate DTW_p, and every
function here dispatches to the exact univariate implementation then,
so d = 1 values are bit-identical by construction.

All device functions take channel-major flattened rows ``(d*n,)`` with a
static ``d`` (repro.mv.layout); the band machinery mirrors
``repro.core.dtw`` cell for cell, with the per-cell cost channel-combined
before it enters the recurrence.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.dtw import (
    BIG,
    PNorm,
    band_dp,
    band_dp_early,
    dtw_banded,
    dtw_banded_diag,
    dtw_banded_early,
    elem_cost,
    finish_cost,
)


def _check_pair_mv(x: jax.Array, y: jax.Array, d: int) -> int:
    if x.ndim != 1 or y.ndim != 1:
        raise ValueError(f"mv dtw expects flat 1-D rows, got {x.shape} / {y.shape}")
    if x.shape[0] != y.shape[0]:
        raise ValueError(
            f"equal flattened lengths required, got {x.shape[0]} != {y.shape[0]}"
        )
    if d < 1 or x.shape[0] % d:
        raise ValueError(f"flat length {x.shape[0]} not a multiple of d={d}")
    return x.shape[0] // d


def _band_costs_mv(x: jax.Array, y: jax.Array, w: int, p: PNorm, d: int):
    """(n, 2w+1) channel-combined cell costs in band coordinates.

    The multivariate twin of ``repro.core.dtw._band_costs``: the gather
    runs per channel on the ``(d, n)`` segment view, the per-scalar costs
    are summed (maxed at p = inf) over the channel axis, and out-of-band
    cells get BIG exactly as in the univariate band.
    """
    n = x.shape[0] // d
    width = 2 * w + 1
    x2 = x.reshape(d, n)
    y2 = y.reshape(d, n)
    rows = jnp.arange(n)[:, None]
    cols = rows + (jnp.arange(width)[None, :] - w)
    valid = (cols >= 0) & (cols < n)
    y_g = y2[:, jnp.clip(cols, 0, n - 1)]  # (d, n, width)
    c = elem_cost(x2[:, :, None] - y_g, p)
    comb = jnp.max(c, axis=0) if p == jnp.inf else jnp.sum(c, axis=0)
    return jnp.where(valid, comb, BIG), valid


@functools.partial(jax.jit, static_argnames=("w", "p", "powered", "d"))
def dtw_banded_mv(
    x: jax.Array,
    y: jax.Array,
    w: int,
    p: PNorm = 1,
    powered: bool = False,
    d: int = 1,
) -> jax.Array:
    """Dependent DTW_p of flattened rows (d*n,) — row-scan form, finite p.

    The same row loop as ``dtw_banded`` (``repro.core.dtw.band_dp``);
    only the cell costs differ (channel-combined).  d = 1 dispatches to the
    univariate implementation verbatim.
    """
    if p == jnp.inf:
        raise ValueError("use dtw_banded_diag_mv for p = inf")
    if d == 1:
        return dtw_banded(x, y, w, p, powered)
    n = _check_pair_mv(x, y, d)
    w = int(min(w, n - 1))
    out = band_dp(*_band_costs_mv(x, y, w, p, d), w)
    return out if powered else finish_cost(out, p)


@functools.partial(jax.jit, static_argnames=("w", "p", "powered", "d"))
def dtw_banded_diag_mv(
    x: jax.Array,
    y: jax.Array,
    w: int,
    p: PNorm = 1,
    powered: bool = False,
    d: int = 1,
) -> jax.Array:
    """Dependent DTW_p via the anti-diagonal wavefront; all p incl. inf."""
    if d == 1:
        return dtw_banded_diag(x, y, w, p, powered)
    n = _check_pair_mv(x, y, d)
    w = int(min(w, n - 1))
    width = 2 * w + 1
    slots = jnp.arange(width)
    x2 = x.reshape(d, n)
    y2 = y.reshape(d, n)

    def diag_cells(dg):
        i2 = dg + (slots - w)
        i = i2 // 2
        j = dg - i
        ok = (i2 % 2 == 0) & (i >= 0) & (i < n) & (j >= 0) & (j < n)
        return i, j, ok

    def step(carry, dg):
        dm1, dm2 = carry
        i, j, ok = diag_cells(dg)
        diff = x2[:, jnp.clip(i, 0, n - 1)] - y2[:, jnp.clip(j, 0, n - 1)]
        cch = elem_cost(diff, p)  # (d, width)
        c = jnp.max(cch, axis=0) if p == jnp.inf else jnp.sum(cch, axis=0)
        up = jnp.concatenate([jnp.array([BIG], dm1.dtype), dm1[:-1]])
        left = jnp.concatenate([dm1[1:], jnp.array([BIG], dm1.dtype)])
        best = jnp.minimum(jnp.minimum(up, left), dm2)
        best = jnp.where((dg == 0) & (slots == w), 0.0, best)
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


@functools.partial(jax.jit, static_argnames=("w", "p", "d"))
def dtw_banded_early_mv(
    x: jax.Array,
    y: jax.Array,
    w: int,
    bound: jax.Array,
    p: PNorm = 1,
    d: int = 1,
) -> jax.Array:
    """Early-abandoning dependent DP (finite p): rows stop once the whole
    band exceeds ``bound`` (powered) — the mv twin of ``dtw_banded_early``,
    abandoned lanes return a value >= bound."""
    if p == jnp.inf:
        raise ValueError("early abandon implemented for finite p")
    if d == 1:
        return dtw_banded_early(x, y, w, bound, p)
    n = _check_pair_mv(x, y, d)
    w = int(min(w, n - 1))
    return band_dp_early(*_band_costs_mv(x, y, w, p, d), w, bound)


def dtw_batch_mv(
    query: jax.Array,
    candidates: jax.Array,
    w: int,
    p: PNorm = 1,
    powered: bool = False,
    d: int = 1,
) -> jax.Array:
    """vmapped dependent DTW: query (d*n,) vs candidates (B, d*n) -> (B,)."""
    if d == 1:
        from repro.core.dtw import dtw_batch

        return dtw_batch(query, candidates, w, p, powered)
    fn = dtw_banded_mv if p != jnp.inf else dtw_banded_diag_mv
    return jax.vmap(lambda c: fn(query, c, w, p, powered, d))(candidates)


def dtw_qbatch_mv(
    queries: jax.Array,
    candidates: jax.Array,
    w: int,
    p: PNorm = 1,
    powered: bool = False,
    d: int = 1,
) -> jax.Array:
    """Dependent DTW over every pair: (Q, d*n) x (B, d*n) -> (Q, B), the
    pairs on one flat vmap axis like ``repro.core.dtw.dtw_qbatch``."""
    if d == 1:
        from repro.core.dtw import dtw_qbatch

        return dtw_qbatch(queries, candidates, w, p, powered)
    fn = dtw_banded_mv if p != jnp.inf else dtw_banded_diag_mv
    nq, nb = queries.shape[0], candidates.shape[0]
    vals = jax.vmap(lambda q, c: fn(q, c, w, p, powered, d))(
        jnp.repeat(queries, nb, axis=0), jnp.tile(candidates, (nq, 1))
    )
    return vals.reshape(nq, nb)


def dtw_reference_mv(x, y, w: int, p: PNorm = 1) -> float:
    """O(n^2 d) float64 numpy oracle for dependent multivariate DTW.

    ``x``/``y`` are channel-minor ``(n, d)`` (a 1-D array is d = 1) —
    the API-facing layout, *not* flattened.  Matches ``dtw_reference``
    exactly at d = 1, including the w >= n unconstrained case; the band
    half-width is interpreted on the per-channel time axis.
    """
    x = np.asarray(x, dtype=np.float64)
    y = np.asarray(y, dtype=np.float64)
    if x.ndim == 1:
        x = x[:, None]
    if y.ndim == 1:
        y = y[:, None]
    if x.shape[1] != y.shape[1]:
        raise ValueError(f"channel mismatch: {x.shape} vs {y.shape}")
    n, m = x.shape[0], y.shape[0]
    w_eff = max(int(w), abs(n - m))
    D = np.full((n + 1, m + 1), np.inf)
    D[0, 0] = 0.0
    for i in range(1, n + 1):
        lo = max(1, i - w_eff)
        hi = min(m, i + w_eff)
        for j in range(lo, hi + 1):
            diff = np.abs(x[i - 1] - y[j - 1])  # (d,)
            if p == np.inf:
                c = diff.max()
            elif p == 1:
                c = diff.sum()
            else:
                c = (diff**p).sum()
            best = min(D[i - 1, j], D[i, j - 1], D[i - 1, j - 1])
            D[i, j] = max(c, best) if p == np.inf else c + best
    q = D[n, m]
    if p in (1, np.inf):
        return float(q)
    return float(q ** (1.0 / p))
