"""Shared helpers for the Pallas TPU kernels.

TPU notes (the kernels are written for TPU and validated on CPU with
``interpret=True``):

* all intermediate arrays are kept >= 2-D — Mosaic requires 2-D iota and
  prefers (sublane, lane) shapes;
* prefix scans (cumsum / cummin / cummax) are implemented with
  Hillis-Steele doubling over static shapes (log2(W) shift+op steps) —
  portable to Mosaic, no dependence on lax.cum* lowering inside kernels;
* sentinels are large-but-finite so fp32 arithmetic never produces
  inf/NaN inside the DP recurrences.
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.core.dtw import BIG, cummin_doubling  # noqa: F401  (the kernels' import)

# finite sentinel; |x - PAD|^2 must stay < fp32 max
PAD_VALUE = 1.0e15


def interpret_default() -> bool:
    """Kernels run interpreted exactly when the backend is not a TPU."""
    import jax

    return jax.default_backend() != "tpu"


def cumsum_doubling(x: jnp.ndarray, axis: int = -1) -> jnp.ndarray:
    """Inclusive prefix sum via Hillis-Steele doubling (static shapes)."""
    n = x.shape[axis]
    shift = 1
    while shift < n:
        pad = [(0, 0)] * x.ndim
        pad[axis] = (shift, 0)
        sl = [slice(None)] * x.ndim
        sl[axis] = slice(0, n)
        x = x + jnp.pad(x, pad)[tuple(sl)]
        shift *= 2
    return x


def cummax_doubling(x: jnp.ndarray, axis: int = -1) -> jnp.ndarray:
    n = x.shape[axis]
    shift = 1
    while shift < n:
        pad = [(0, 0)] * x.ndim
        pad[axis] = (shift, 0)
        sl = [slice(None)] * x.ndim
        sl[axis] = slice(0, n)
        x = jnp.maximum(x, jnp.pad(x, pad, constant_values=-BIG)[tuple(sl)])
        shift *= 2
    return x


def round_up(x: int, m: int) -> int:
    return -(-x // m) * m
