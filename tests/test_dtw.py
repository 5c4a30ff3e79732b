"""Banded DTW vs the O(n^2) numpy oracle, all execution paths."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.cascade import _dtw_pairs_block
from repro.core.dtw import (
    BIG,
    WIDE_BATCH,
    _band_costs,
    dtw_banded,
    dtw_banded_diag,
    dtw_banded_early,
    dtw_batch,
    dtw_qbatch,
    dtw_reference,
)
from repro.mv.dtw import _band_costs_mv, dtw_banded_early_mv, dtw_banded_mv

RNG = np.random.default_rng(42)


def _pair(n):
    x = RNG.normal(size=n).astype(np.float32).cumsum()
    y = RNG.normal(size=n).astype(np.float32).cumsum()
    return x, y


@pytest.mark.parametrize("n", [4, 17, 64, 101])
@pytest.mark.parametrize("w", [1, 3, 10])
@pytest.mark.parametrize("p", [1, 2])
def test_row_scan_matches_oracle(n, w, p):
    x, y = _pair(n)
    ref = dtw_reference(x, y, w, p)
    got = float(dtw_banded(jnp.asarray(x), jnp.asarray(y), w, p))
    assert abs(got - ref) <= 1e-3 * max(1.0, abs(ref))


@pytest.mark.parametrize("n", [4, 33, 80])
@pytest.mark.parametrize("w", [1, 7])
@pytest.mark.parametrize("p", [1, 2, jnp.inf])
def test_diag_scan_matches_oracle(n, w, p):
    x, y = _pair(n)
    ref = dtw_reference(x, y, w, np.inf if p == jnp.inf else p)
    got = float(dtw_banded_diag(jnp.asarray(x), jnp.asarray(y), w, p))
    assert abs(got - ref) <= 1e-3 * max(1.0, abs(ref))


def test_unconstrained_band_equals_full_dtw():
    x, y = _pair(24)
    ref = dtw_reference(x, y, 24, 1)  # w >= n: unconstrained
    got = float(dtw_banded(jnp.asarray(x), jnp.asarray(y), 50, 1))
    assert abs(got - ref) <= 1e-3 * max(1.0, abs(ref))


def test_w0_is_lp_distance():
    x, y = _pair(31)
    got = float(dtw_banded(jnp.asarray(x), jnp.asarray(y), 0, 1))
    assert abs(got - np.abs(x - y).sum()) < 1e-2


def test_identity_is_zero():
    x, _ = _pair(50)
    assert float(dtw_banded(jnp.asarray(x), jnp.asarray(x), 5, 1)) < 1e-4


def test_symmetry():
    x, y = _pair(40)
    a = float(dtw_banded(jnp.asarray(x), jnp.asarray(y), 4, 1))
    b = float(dtw_banded(jnp.asarray(y), jnp.asarray(x), 4, 1))
    assert abs(a - b) < 1e-3 * max(1.0, a)


def test_batch_matches_single():
    q, _ = _pair(60)
    cands = np.stack([_pair(60)[1] for _ in range(7)])
    batch = np.asarray(dtw_batch(jnp.asarray(q), jnp.asarray(cands), 6, 1))
    for i in range(7):
        single = float(dtw_banded(jnp.asarray(q), jnp.asarray(cands[i]), 6, 1))
        assert abs(batch[i] - single) < 1e-3 * max(1.0, abs(single))


def test_row_and_diag_agree():
    for n, w in [(16, 2), (55, 11), (90, 30)]:
        x, y = _pair(n)
        a = float(dtw_banded(jnp.asarray(x), jnp.asarray(y), w, 2))
        b = float(dtw_banded_diag(jnp.asarray(x), jnp.asarray(y), w, 2))
        assert abs(a - b) <= 1e-3 * max(1.0, abs(a))


# --- the row loop against the per-row cumsum + lax.cummin step it replaced


def _old_rows(costs, valid, w, bound=None):
    """The row step with ``cumsum`` and ``lax.cummin`` inside the row loop
    (scan, or the early-abandoning while loop when ``bound`` is given)."""
    n, width = costs.shape
    costs_sum = jnp.where(valid, costs, 0.0)
    prev0 = jnp.full((width,), BIG, costs.dtype).at[w].set(0.0)

    def row(prev, cost_sum_row, valid_row):
        up = jnp.concatenate([prev[1:], jnp.array([BIG], prev.dtype)])
        b = jnp.minimum(up, prev)
        s = jnp.cumsum(cost_sum_row)
        t = jnp.where(valid_row, b + cost_sum_row - s, BIG)
        out = jnp.minimum(s + jax.lax.cummin(t), BIG)
        return jnp.where(valid_row, out, BIG)

    if bound is None:
        last, _ = jax.lax.scan(
            lambda prev, r: (row(prev, *r), None), prev0, (costs_sum, valid)
        )
        return last[w]

    def cond(state):
        i, prev = state
        return (i < n) & (jnp.min(prev) < bound)

    def step(state):
        i, prev = state
        return i + 1, row(prev, costs_sum[i], valid[i])

    i, last = jax.lax.while_loop(cond, step, (jnp.int32(0), prev0))
    return jnp.where(i == n, last[w], jnp.min(last))


@functools.partial(jax.jit, static_argnames=("w", "p", "d"))
def _old_dtw(x, y, w, p, d, bound=None):
    """The replaced powered DTW, cell costs included, in one program."""
    costs = _band_costs_mv(x, y, w, p, d) if d > 1 else _band_costs(x, y, w, p)
    return _old_rows(*costs, w, bound)


MV_D = 3


def _walks(n, d=1):
    x = RNG.normal(size=n * d).astype(np.float32).reshape(d, n).cumsum(axis=1)
    y = RNG.normal(size=n * d).astype(np.float32).reshape(d, n).cumsum(axis=1)
    return jnp.asarray(x.ravel()), jnp.asarray(y.ravel())


BIT_CASES = [(1000, 100, 1), (128, 6, 2), (64, 63, 1), (200, 10, 3)]


@pytest.mark.parametrize("n,w,p", BIT_CASES)
@pytest.mark.parametrize(
    "fn", ["banded", "early_inf", "early_abandon", "banded_mv", "early_mv"]
)
def test_row_loop_bit_identical_to_in_loop_scans(n, w, p, fn):
    mv = fn.endswith("_mv")
    d = MV_D if mv else 1
    x, y = _walks(n, d)
    full = _old_dtw(x, y, w, p, d)
    if fn == "banded":
        got, want = dtw_banded(x, y, w, p, powered=True), full
    elif fn == "banded_mv":
        got, want = dtw_banded_mv(x, y, w, p, powered=True, d=d), full
    else:
        bound = jnp.float32(np.inf if fn == "early_inf" else 0.5 * float(full))
        want = _old_dtw(x, y, w, p, d, bound)
        if fn == "early_mv":
            got = dtw_banded_early_mv(x, y, w, bound, p, d)
        else:
            got = dtw_banded_early(x, y, w, bound, p)
        if fn == "early_abandon":
            assert float(bound) <= float(want) < float(full)  # it abandoned
    assert np.array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("n,w,p", BIT_CASES)
@pytest.mark.parametrize("nq,nb", [(2, 3), (8, 16)])
def test_pairs_block_bit_identical_to_qbatch(n, w, p, nq, nb):
    """The host driver's 16-pair chunks against one dense batch of the
    same pairs: 6 pairs (both hoist S) and 128 (the batch keeps S in the
    row loop, the chunks hoist it)."""
    qs = np.stack([np.asarray(_walks(n)[0]) for _ in range(nq)])
    cs = np.stack([np.asarray(_walks(n)[1]) for _ in range(nb)])
    want = np.asarray(
        dtw_qbatch(jnp.asarray(qs), jnp.asarray(cs), w, p, powered=True)
    )
    qrows = np.repeat(qs, nb, axis=0)
    crows = np.tile(cs, (nq, 1))
    got = np.concatenate(
        [
            np.asarray(
                _dtw_pairs_block(
                    jnp.asarray(qrows[i : i + 16]), jnp.asarray(crows[i : i + 16]), w, p
                )
            )
            for i in range(0, nq * nb, 16)
        ]
    ).reshape(nq, nb)
    assert np.array_equal(got, want)


def _loop_bodies(jaxpr, inside=False):
    """Yield (primitive name, inside a scan/while body) for every equation."""
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name, inside
        loop = inside or eqn.primitive.name in ("scan", "while")
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _loop_bodies(sub, loop)


@pytest.mark.parametrize("lanes", [None, 16, 128])
@pytest.mark.parametrize("fn", ["banded", "early", "banded_mv", "early_mv"])
def test_no_prefix_scan_primitive_in_row_loop(fn, lanes):
    """No ``cummin`` in any row loop, and no ``cumsum`` in one that runs
    alone or vmapped over fewer than ``WIDE_BATCH`` pairs: those take S
    before the loop.  From ``WIDE_BATCH`` pairs on the cumsum stays in
    the loop (``repro.core.dtw._by_batch``)."""
    d = MV_D if fn.endswith("_mv") else 1
    x, y = _walks(64, d)
    inf = jnp.float32(np.inf)
    one = {
        "banded": lambda a, b: dtw_banded(a, b, 10, 1),
        "early": lambda a, b: dtw_banded_early(a, b, 10, inf, 1),
        "banded_mv": lambda a, b: dtw_banded_mv(a, b, 10, 1, d=d),
        "early_mv": lambda a, b: dtw_banded_early_mv(a, b, 10, inf, 1, d),
    }[fn]
    if lanes is None:
        jaxpr = jax.make_jaxpr(one)(x, y)
    else:
        jaxpr = jax.make_jaxpr(jax.vmap(one))(
            jnp.tile(x, (lanes, 1)), jnp.tile(y, (lanes, 1))
        )
    prims = list(_loop_bodies(jaxpr.jaxpr))
    in_loop = {name for name, inside in prims if inside}
    assert "min" in in_loop  # the doubling cummin is in the loop
    assert not in_loop & {"cummin", "cummax", "cumprod", "cumlogsumexp"}
    hoisted = lanes is None or lanes < WIDE_BATCH
    assert ("cumsum" in in_loop) != hoisted
    assert (("cumsum", False) in prims) == hoisted
