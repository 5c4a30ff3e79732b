"""Pallas kernels vs their ref.py oracles: shape/dtype sweeps (interpret)."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.dtw import dtw_reference
from repro.core.envelope import envelope, envelope_batch, envelope_naive
from repro.core.lb import lb_keogh_powered_qbatch
from repro.kernels import (
    dtw_early_ref,
    dtw_op,
    dtw_ref,
    lb_fused_qbatch_op,
    lb_fused_qbatch_ref,
    envelope_op,
    envelope_ref,
    lb_improved_op,
    lb_improved_qbatch_op,
    lb_improved_qbatch_ref,
    lb_improved_ref,
    lb_improved_stream_qbatch_op,
    lb_improved_stream_qbatch_ref,
    lb_keogh_op,
    lb_keogh_qbatch_op,
    lb_keogh_qbatch_ref,
    lb_keogh_ref,
    lb_keogh_stream_qbatch_op,
    lb_keogh_stream_qbatch_ref,
    lb_kim_qbatch_op,
    lb_kim_qbatch_ref,
    materialize_windows,
)

RNG = np.random.default_rng(5)


def test_interpret_follows_the_backend_only():
    """Kernels are interpreted exactly off the TPU: no switch can make a
    chip run interpret them, or the CPU compile them."""
    import inspect

    import jax

    from repro.kernels import common

    assert common.interpret_default() is (jax.default_backend() != "tpu")
    assert "environ" not in inspect.getsource(common)


SHAPES = [(4, 32, 3), (8, 100, 10), (3, 65, 16), (16, 128, 12), (5, 47, 46)]


@pytest.mark.parametrize("b,n,w", SHAPES)
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_envelope_kernel(b, n, w, dtype):
    xs = RNG.normal(size=(b, n)).astype(np.float32).cumsum(axis=1)
    xs = jnp.asarray(xs, dtype)
    u, l = envelope_op(xs, w, interpret=True)
    ur, lr = envelope_ref(xs, w)
    rtol = 1e-6 if dtype == np.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(u, np.float32), np.asarray(ur, np.float32), rtol=rtol
    )
    np.testing.assert_allclose(
        np.asarray(l, np.float32), np.asarray(lr, np.float32), rtol=rtol
    )


@pytest.mark.parametrize("b,n,w", SHAPES)
@pytest.mark.parametrize("p", [1, 2])
def test_lb_keogh_kernel(b, n, w, p):
    xs = RNG.normal(size=(b, n)).astype(np.float32).cumsum(axis=1)
    q = RNG.normal(size=n).astype(np.float32).cumsum()
    u, l = envelope(jnp.asarray(q), w)
    lb, h = lb_keogh_op(jnp.asarray(xs), u, l, p, interpret=True)
    lbr, hr = lb_keogh_ref(jnp.asarray(xs), u, l, p)
    np.testing.assert_allclose(np.asarray(lb), np.asarray(lbr), rtol=1e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr), rtol=1e-6)


@pytest.mark.parametrize("b,n,w", SHAPES)
@pytest.mark.parametrize("p", [1, 2])
def test_lb_improved_kernel(b, n, w, p):
    """Full two-pass kernel chain vs the pure-jnp Corollary 4 oracle."""
    xs = RNG.normal(size=(b, n)).astype(np.float32).cumsum(axis=1)
    q = jnp.asarray(RNG.normal(size=n).astype(np.float32).cumsum())
    u, l = envelope(q, w)
    got = lb_improved_op(jnp.asarray(xs), q, u, l, w, p, interpret=True)
    want = lb_improved_ref(jnp.asarray(xs), q, u, l, w, p)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4)


QBATCH_SHAPES = [(3, 10, 64, 7), (5, 8, 100, 10), (2, 13, 47, 46)]


@pytest.mark.parametrize("nq,b,n,w", QBATCH_SHAPES)
@pytest.mark.parametrize("p", [1, 2])
def test_lb_keogh_qbatch_kernel(nq, b, n, w, p):
    """Query-grid kernel (DESIGN.md §3.4) vs the query-major oracle."""
    xs = RNG.normal(size=(b, n)).astype(np.float32).cumsum(axis=1)
    qs = RNG.normal(size=(nq, n)).astype(np.float32).cumsum(axis=1)
    u, l = envelope_batch(jnp.asarray(qs), w)
    lb, h = lb_keogh_qbatch_op(jnp.asarray(xs), u, l, p, interpret=True)
    lbr, hr = lb_keogh_qbatch_ref(jnp.asarray(xs), u, l, p)
    assert lb.shape == (nq, b) and h.shape == (nq, b, n)
    np.testing.assert_allclose(np.asarray(lb), np.asarray(lbr), rtol=1e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr), rtol=1e-6)


@pytest.mark.parametrize("nq,b,n,w", QBATCH_SHAPES)
@pytest.mark.parametrize("p", [1, 2])
def test_lb_improved_qbatch_kernel(nq, b, n, w, p):
    """Query-grid two-pass chain vs the pure-jnp Corollary 4 oracle."""
    xs = RNG.normal(size=(b, n)).astype(np.float32).cumsum(axis=1)
    qs = jnp.asarray(RNG.normal(size=(nq, n)).astype(np.float32).cumsum(axis=1))
    u, l = envelope_batch(qs, w)
    got = lb_improved_qbatch_op(jnp.asarray(xs), qs, u, l, w, p, interpret=True)
    want = lb_improved_qbatch_ref(jnp.asarray(xs), qs, u, l, w, p)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4)


def test_qbatch_kernel_rows_match_single_query_kernel():
    """Each query lane of the batched kernel equals the per-query kernel."""
    b, n, w, p = 9, 80, 8, 2
    xs = jnp.asarray(RNG.normal(size=(b, n)).astype(np.float32).cumsum(axis=1))
    qs = jnp.asarray(RNG.normal(size=(4, n)).astype(np.float32).cumsum(axis=1))
    u, l = envelope_batch(qs, w)
    lb_b, h_b = lb_keogh_qbatch_op(xs, u, l, p, interpret=True)
    imp_b = lb_improved_qbatch_op(xs, qs, u, l, w, p, interpret=True)
    for i in range(4):
        lb_s, h_s = lb_keogh_op(xs, u[i], l[i], p, interpret=True)
        np.testing.assert_allclose(np.asarray(lb_b[i]), np.asarray(lb_s), rtol=1e-6)
        np.testing.assert_allclose(np.asarray(h_b[i]), np.asarray(h_s), rtol=1e-6)
        imp_s = lb_improved_op(xs, qs[i], u[i], l[i], w, p, interpret=True)
        np.testing.assert_allclose(np.asarray(imp_b[i]), np.asarray(imp_s), rtol=1e-5)


STREAM_SHAPES = [  # (nq, n, w, hop, L)
    (3, 32, 4, 1, 95),
    (2, 40, 8, 3, 160),
    (4, 24, 23, 5, 130),
    (2, 16, 2, 16, 97),  # hop == n: non-overlapping windows
]


@pytest.mark.parametrize("nq,n,w,hop,L", STREAM_SHAPES)
@pytest.mark.parametrize("p", [1, 2])
def test_lb_keogh_stream_kernel(nq, n, w, hop, L, p):
    """Stream-packed kernel (window lanes sliced from a flat segment in
    VMEM, DESIGN.md §3.5) vs the materialized-window oracle."""
    seg = jnp.asarray(RNG.normal(size=L).astype(np.float32).cumsum())
    qs = jnp.asarray(RNG.normal(size=(nq, n)).astype(np.float32).cumsum(axis=1))
    u, l = envelope_batch(qs, w)
    lb, h = lb_keogh_stream_qbatch_op(seg, u, l, n, hop, p, interpret=True)
    lbr, hr = lb_keogh_stream_qbatch_ref(seg, u, l, n, hop, p)
    b = (L - n) // hop + 1
    assert lb.shape == (nq, b) and h.shape == (nq, b, n)
    np.testing.assert_allclose(np.asarray(lb), np.asarray(lbr), rtol=1e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr), rtol=1e-6)


@pytest.mark.parametrize("nq,n,w,hop,L", STREAM_SHAPES)
@pytest.mark.parametrize("p", [1, 2])
def test_lb_improved_stream_kernel(nq, n, w, hop, L, p):
    """Stream pass 1 feeding the existing query-major pass 2 equals the
    materialized two-pass oracle."""
    seg = jnp.asarray(RNG.normal(size=L).astype(np.float32).cumsum())
    qs = jnp.asarray(RNG.normal(size=(nq, n)).astype(np.float32).cumsum(axis=1))
    u, l = envelope_batch(qs, w)
    got = lb_improved_stream_qbatch_op(seg, qs, u, l, n, w, hop, p, interpret=True)
    want = lb_improved_stream_qbatch_ref(seg, qs, u, l, n, w, hop, p)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4)


def test_stream_kernel_equals_materialized_qbatch_kernel():
    """The segment-sliced lanes are exactly the rows the materialized
    qbatch kernel would see."""
    nq, n, w, hop, L, p = 3, 30, 5, 2, 120, 2
    seg = jnp.asarray(RNG.normal(size=L).astype(np.float32).cumsum())
    qs = jnp.asarray(RNG.normal(size=(nq, n)).astype(np.float32).cumsum(axis=1))
    u, l = envelope_batch(qs, w)
    wins = materialize_windows(seg, n, hop)
    lb_s, h_s = lb_keogh_stream_qbatch_op(seg, u, l, n, hop, p, interpret=True)
    lb_m, h_m = lb_keogh_qbatch_op(wins, u, l, p, interpret=True)
    np.testing.assert_array_equal(np.asarray(lb_s), np.asarray(lb_m))
    np.testing.assert_array_equal(np.asarray(h_s), np.asarray(h_m))


@pytest.mark.parametrize("b,n,w", SHAPES)
@pytest.mark.parametrize("p", [1, 2])
def test_dtw_kernel(b, n, w, p):
    xs = RNG.normal(size=(b, n)).astype(np.float32).cumsum(axis=1)
    q = RNG.normal(size=n).astype(np.float32).cumsum()
    d = dtw_op(jnp.asarray(q), jnp.asarray(xs), w, p, interpret=True)
    dr = dtw_ref(jnp.asarray(q), jnp.asarray(xs), w, p)
    np.testing.assert_allclose(np.asarray(d), np.asarray(dr), rtol=3e-4)
    # spot-check one lane against the numpy DP oracle
    ref0 = dtw_reference(q, xs[0], w, p)
    assert abs(float(d[0]) - ref0) <= 1e-3 * max(1.0, abs(ref0))


def test_dtw_kernel_powered():
    xs = RNG.normal(size=(4, 64)).astype(np.float32).cumsum(axis=1)
    q = RNG.normal(size=64).astype(np.float32).cumsum()
    d2 = dtw_op(jnp.asarray(q), jnp.asarray(xs), 6, 2, powered=True, interpret=True)
    d = dtw_op(jnp.asarray(q), jnp.asarray(xs), 6, 2, powered=False, interpret=True)
    np.testing.assert_allclose(np.asarray(d) ** 2, np.asarray(d2), rtol=1e-3)


@pytest.mark.parametrize("b,n,w", SHAPES)
@pytest.mark.parametrize("p", [1, 2])
def test_dtw_kernel_early_abandon(b, n, w, p):
    """While-loop kernel vs ``dtw_banded_early`` (the host twin): exact
    below the bound, >= bound when abandoned, bit-matched either way."""
    xs = RNG.normal(size=(b, n)).astype(np.float32).cumsum(axis=1)
    q = RNG.normal(size=n).astype(np.float32).cumsum()
    true = np.array([dtw_reference(q, c, w, p) for c in xs])
    true_pow = true if p == 1 else true**p
    # bounds straddling the true distances: some lanes abandon, some not
    fracs = np.resize([0.2, 0.7, 1.0, 1.4], b)
    bounds = (true_pow * fracs).astype(np.float32)
    got = np.asarray(
        dtw_op(
            jnp.asarray(q), jnp.asarray(xs), w, p,
            powered=True, bounds=jnp.asarray(bounds), interpret=True,
        )
    )
    want = np.asarray(
        dtw_early_ref(jnp.asarray(q), jnp.asarray(xs), w, jnp.asarray(bounds), p)
    )
    np.testing.assert_allclose(got, want, rtol=1e-5)
    abandoned = 0
    for i in range(b):
        if got[i] < bounds[i]:  # finished: exact powered DTW
            np.testing.assert_allclose(
                got[i], true_pow[i], rtol=3e-4, atol=1e-5
            )
        else:  # abandoned: still a valid lower bound
            abandoned += 1
            assert true_pow[i] >= bounds[i] - 1e-3 * max(1.0, abs(true_pow[i]))
    assert abandoned > 0  # the sweep must actually exercise abandonment


@pytest.mark.parametrize("nq,b,n,w", QBATCH_SHAPES)
@pytest.mark.parametrize("p", [1, 2])
def test_lb_fused_kernel(nq, b, n, w, p):
    """Single-launch fused LB_Keogh -> LB_Improved (DESIGN.md §3.6) vs
    the dense two-kernel oracle, pass 2 predicated per lane."""
    xs = jnp.asarray(RNG.normal(size=(b, n)).astype(np.float32).cumsum(axis=1))
    qs = jnp.asarray(RNG.normal(size=(nq, n)).astype(np.float32).cumsum(axis=1))
    u, l = envelope_batch(qs, w)
    lb1_true = np.asarray(lb_keogh_powered_qbatch(xs, u, l, p))
    # per-query bounds that keep ~40% of lanes alive into pass 2
    bounds = jnp.asarray(np.quantile(lb1_true, 0.4, axis=1).astype(np.float32))
    lb1, lb = lb_fused_qbatch_op(xs, qs, u, l, w, bounds, p, interpret=True)
    lb1r, lbr = lb_fused_qbatch_ref(xs, qs, u, l, w, bounds, p)
    np.testing.assert_allclose(np.asarray(lb1), np.asarray(lb1r), rtol=1e-4)
    np.testing.assert_allclose(np.asarray(lb), np.asarray(lbr), rtol=2e-4)
    # pruned lanes must carry lb1 unchanged (pass 2 predicated away)
    dead = np.asarray(lb1) >= np.asarray(bounds)[:, None]
    np.testing.assert_array_equal(np.asarray(lb)[dead], np.asarray(lb1)[dead])
    assert dead.any() and (~dead).any()


def test_lb_fused_kernel_matches_unfused_chain():
    """The fused kernel's alive lanes equal the two-launch kernel chain
    (lb_keogh_qbatch_op + pass 2) — same values, one HBM sweep."""
    nq, b, n, w, p = 4, 16, 80, 8, 2
    xs = jnp.asarray(RNG.normal(size=(b, n)).astype(np.float32).cumsum(axis=1))
    qs = jnp.asarray(RNG.normal(size=(nq, n)).astype(np.float32).cumsum(axis=1))
    u, l = envelope_batch(qs, w)
    bounds = jnp.full((nq,), 1e30, jnp.float32)  # everything alive
    _, lb = lb_fused_qbatch_op(xs, qs, u, l, w, bounds, p, interpret=True)
    chain = lb_improved_qbatch_op(xs, qs, u, l, w, p, interpret=True)
    np.testing.assert_allclose(np.asarray(lb), np.asarray(chain), rtol=1e-5)


@pytest.mark.parametrize("nq,b,n,w", QBATCH_SHAPES)
@pytest.mark.parametrize("p", [1, 2, np.inf])
def test_lb_kim_qbatch_kernel(nq, b, n, w, p):
    """Constant-time LB_Kim stage-0 kernel vs the core/lb oracle —
    including the ragged final block (b not a multiple of tile_b, the
    op pads candidates with PAD_VALUE and slices back)."""
    del w  # LB_Kim is band-free
    xs = jnp.asarray(RNG.normal(size=(b, n)).astype(np.float32).cumsum(axis=1))
    qs = jnp.asarray(RNG.normal(size=(nq, n)).astype(np.float32).cumsum(axis=1))
    got = lb_kim_qbatch_op(xs, qs, p=p, tile_b=8, interpret=True)
    want = lb_kim_qbatch_ref(xs, qs, p=p)
    assert got.shape == (nq, b)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4)


@pytest.mark.parametrize("p", [1, 2, np.inf])
def test_lb_kim_qbatch_kernel_entry_mask(p):
    """Masked-out lanes (already pruned upstream, or poison padding)
    must come back as BIG and never contribute their data; alive lanes
    must be untouched by their dead neighbours."""
    nq, b, n = 3, 13, 40  # ragged: 13 lanes over tile_b=8
    xs = jnp.asarray(RNG.normal(size=(b, n)).astype(np.float32).cumsum(axis=1))
    qs = jnp.asarray(RNG.normal(size=(nq, n)).astype(np.float32).cumsum(axis=1))
    mask = jnp.asarray(RNG.integers(0, 2, size=(nq, b)).astype(np.float32))
    got = np.asarray(lb_kim_qbatch_op(xs, qs, mask=mask, p=p, tile_b=8, interpret=True))
    want = np.asarray(lb_kim_qbatch_ref(xs, qs, mask=mask, p=p))
    np.testing.assert_allclose(got, want, rtol=2e-4)
    m = np.asarray(mask) > 0
    assert (got[~m] >= 1e29).all()  # dead lanes carry the BIG sentinel
    bare = np.asarray(lb_kim_qbatch_op(xs, qs, p=p, tile_b=8, interpret=True))
    np.testing.assert_array_equal(got[m], bare[m])


def test_envelope_kernel_odd_batch_padding():
    xs = RNG.normal(size=(3, 33)).astype(np.float32)
    u, l = envelope_op(jnp.asarray(xs), 4, tile_b=8, interpret=True)
    for i in range(3):
        un, ln = envelope_naive(xs[i], 4)
        np.testing.assert_allclose(np.asarray(u[i]), un, rtol=1e-6)
        np.testing.assert_allclose(np.asarray(l[i]), ln, rtol=1e-6)
