"""Plain banded DTW for the benchmark's comparisons.

Nothing here imports the program.  ``dtw_numpy`` is the textbook
recurrence cell by cell; ``dtw_pairs`` computes the same recurrence for
many pairs at once on the device, one anti-diagonal of the band per
step, so that each cell sees exactly the textbook's operations:

    D[i, j] = cost(x[i], y[j]) + min(D[i-1, j-1], D[i-1, j], D[i, j-1])

over the Sakoe-Chiba band |i - j| <= w, with D[-1, -1] = 0.  The cost is
|x - y| for p = 1 and (x - y)^2 for p = 2, and the distance is D[n-1,
n-1] (p = 1) or its square root (p = 2).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

SUPPORTED_P = (1, 2)


def _check_p(p) -> int:
    if p not in SUPPORTED_P:
        raise ValueError(f"the reference DTW covers p in {SUPPORTED_P}, got {p!r}")
    return int(p)


def dtw_numpy(x, y, w: int, p: int) -> float:
    """Textbook banded DTW_p of two equal-length series, in float64."""
    _check_p(p)
    x = np.asarray(x, np.float64)
    y = np.asarray(y, np.float64)
    n = len(x)
    big = np.inf
    D = np.full((n + 1, n + 1), big)
    D[0, 0] = 0.0
    for i in range(1, n + 1):
        for j in range(max(1, i - w), min(n, i + w) + 1):
            d = x[i - 1] - y[j - 1]
            c = abs(d) if p == 1 else d * d
            D[i, j] = c + min(D[i - 1, j - 1], D[i - 1, j], D[i, j - 1])
    return float(D[n, n] if p == 1 else np.sqrt(D[n, n]))


@functools.partial(jax.jit, static_argnames=("w", "p"))
def dtw_pairs(x: jax.Array, y: jax.Array, w: int, p: int) -> jax.Array:
    """DTW_p of every column pair: ``x``, ``y`` are (n, P), time along
    the first axis; returns (P,) distances in the inputs' dtype.

    The band of one anti-diagonal d = i + j is held in 2w + 1 slots, slot
    e for the cell with i - j = e - w (half the slots of a diagonal hold
    no cell and stay at +inf).  From diagonal d - 1, cell (i - 1, j) is
    slot e - 1 and cell (i, j - 1) is slot e + 1; from d - 2, cell
    (i - 1, j - 1) is slot e.
    """
    p = _check_p(p)
    n, pairs = x.shape
    width = 2 * w + 1
    dtype = x.dtype
    slot = jnp.arange(width)[:, None]
    inf_row = jnp.full((1, pairs), jnp.inf, dtype)

    def step(carry, d):
        prev, prev2 = carry
        two_i = d + slot[:, 0] - w
        i = two_i // 2
        j = d - i
        ok = ((two_i % 2) == 0) & (i >= 0) & (i < n) & (j >= 0) & (j < n)
        diff = x[jnp.clip(i, 0, n - 1)] - y[jnp.clip(j, 0, n - 1)]
        cost = jnp.abs(diff) if p == 1 else diff * diff
        up = jnp.concatenate([inf_row, prev[:-1]], axis=0)
        left = jnp.concatenate([prev[1:], inf_row], axis=0)
        best = jnp.minimum(jnp.minimum(up, left), prev2)
        origin = (d == 0) & (slot == w)
        best = jnp.where(origin, jnp.zeros((), dtype), best)
        cur = jnp.where(ok[:, None], cost + best, jnp.inf).astype(dtype)
        return (cur, prev), None

    init = jnp.full((width, pairs), jnp.inf, dtype)
    (last, _), _ = jax.lax.scan(step, (init, init), jnp.arange(2 * n - 1))
    acc = last[w]
    return acc if p == 1 else jnp.sqrt(acc)


@functools.partial(jax.jit, static_argnames=("w", "p", "dtype"))
def dtw_cross(a: jax.Array, b: jax.Array, w: int, p: int, dtype) -> jax.Array:
    """DTW_p of every row of ``a`` (A, n) against every row of ``b``
    (B, n), computed in ``dtype``; returns (A, B) in ``dtype``."""
    na, n = a.shape
    nb = b.shape[0]
    x = jnp.broadcast_to(a.T[:, :, None], (n, na, nb)).reshape(n, na * nb)
    y = jnp.broadcast_to(b.T[:, None, :], (n, na, nb)).reshape(n, na * nb)
    return dtw_pairs(x.astype(dtype), y.astype(dtype), w, p).reshape(na, nb)


def cross_distances(
    a: np.ndarray,
    b: np.ndarray,
    w: int,
    p: int,
    *,
    dtype: str = "float32",
    pairs_per_call: int = 1 << 15,
) -> np.ndarray:
    """(A, B) DTW distances in ``dtype``, returned as float64, computed
    ``pairs_per_call`` pairs at a time so that the device holds one
    slice of rows and its work, never all of it."""
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    out = np.empty((a.shape[0], b.shape[0]), np.float64)
    rows_b = max(1, min(b.shape[0], pairs_per_call))
    rows_a = max(1, pairs_per_call // rows_b)
    dt = jnp.dtype(dtype)
    for i0 in range(0, a.shape[0], rows_a):
        for j0 in range(0, b.shape[0], rows_b):
            blk_a, blk_b = a[i0 : i0 + rows_a], b[j0 : j0 + rows_b]
            if blk_a.shape[0] < rows_a:  # keep one shape: pad, then drop
                blk_a = np.concatenate(
                    [blk_a, np.repeat(blk_a[-1:], rows_a - blk_a.shape[0], 0)]
                )
            if blk_b.shape[0] < rows_b:
                blk_b = np.concatenate(
                    [blk_b, np.repeat(blk_b[-1:], rows_b - blk_b.shape[0], 0)]
                )
            d = np.asarray(dtw_cross(blk_a, blk_b, w, p, dt), np.float64)
            na = min(rows_a, a.shape[0] - i0)
            nb = min(rows_b, b.shape[0] - j0)
            out[i0 : i0 + na, j0 : j0 + nb] = d[:na, :nb]
    return out
