"""The plain references and the comparisons that decide ``correct``,
on the CPU at small sizes."""

from __future__ import annotations

import numpy as np
import pytest

from bench_testing import ROOT  # noqa: F401  (puts the checkout on the path)

from bench import generate
from bench.reference import dtw as rdtw
from bench.reference import knn as rknn
from bench.reference import stream as rstream


def _walks(seed, count, n):
    return generate.random_walks(np.random.default_rng(seed), count, n)


@pytest.mark.parametrize("p", [1, 2])
@pytest.mark.parametrize("n,w", [(1, 0), (7, 2), (40, 4), (33, 32)])
def test_device_dtw_matches_the_textbook_recurrence(p, n, w):
    a, b = _walks(0, 3, n), _walks(1, 5, n)
    got = rdtw.cross_distances(a, b, w, p)
    want = np.array([[rdtw.dtw_numpy(x, y, w, p) for y in b] for x in a])
    np.testing.assert_allclose(got, want, rtol=2e-6)


def test_bfloat16_reference_is_visibly_coarser():
    a, b = _walks(2, 4, 64), _walks(3, 16, 64)
    f32 = rdtw.cross_distances(a, b, 6, 1)
    bf16 = rdtw.cross_distances(a, b, 6, 1, dtype="bfloat16")
    assert np.max(np.abs(bf16 - f32) / f32) > 1e-3


def test_blocked_calls_equal_one_call():
    a, b = _walks(4, 5, 24), _walks(5, 7, 24)
    one = rdtw.cross_distances(a, b, 3, 2, pairs_per_call=1 << 20)
    many = rdtw.cross_distances(a, b, 3, 2, pairs_per_call=6)
    np.testing.assert_array_equal(one, many)


def test_knn_compare_passes_on_the_answer_and_fails_on_faults():
    dist = rknn.all_distances(_walks(6, 3, 32), _walks(7, 50, 32), 3, 1)
    d, i = rknn.topk(dist, 5)
    ok = rknn.compare(d, i, dist)
    assert ok == {"dist_gap": 0.0, "index_gap": 0.0}
    worse = d.copy()
    worse[1, 2] *= 1 + 1e-3
    assert rknn.compare(worse, i, dist)["dist_gap"] > 5e-4
    swapped = i.copy()
    swapped[0, 0] = i[0, 4] if i[0, 4] != i[0, 0] else i[0, 1]
    assert rknn.compare(d, swapped, dist)["index_gap"] > 1e-4
    outside = i.copy()
    outside[2, 1] = 50
    assert rknn.compare(d, outside, dist)["index_gap"] == np.inf


def test_knn_compare_accepts_either_order_of_a_tie():
    dist = np.array([[3.0, 1.0, 1.0, 2.0]])
    assert rknn.compare(np.array([[1.0, 1.0]]), np.array([[2, 1]]), dist) == {
        "dist_gap": 0.0, "index_gap": 0.0}


def _brute_greedy(dist, thr, excl):
    hits = [(dist[t, s], s, t) for t in range(dist.shape[0])
            for s in range(dist.shape[1]) if dist[t, s] <= thr[t]]
    kept = []
    for d, s, t in sorted(hits):
        if all(not (t2 == t and abs(s2 - s) < excl) for t2, s2, _ in kept):
            kept.append((t, s, d))
    return sorted(kept)


def test_greedy_exclusion_matches_the_rule():
    rng = np.random.default_rng(8)
    dist = rng.uniform(0, 1, (3, 200))
    thr = np.array([0.2, 0.05, 0.5])
    got = sorted(rstream.greedy_exclusion(dist, thr, 10))
    assert got == _brute_greedy(dist, thr, 10)


def _stream_case(seed=9):
    rng = np.random.default_rng(seed)
    templates = generate.random_walks(rng, 3, 32)
    stream, _ = generate.planted_walk_stream(
        rng, 3000, templates, every=300, amp_range=(0.8, 1.2), noise=0.05
    )
    dist = rstream.window_distances(stream, templates, 3, 2)
    thr = np.quantile(dist, 0.02, axis=1)
    return dist, thr


def test_stream_compare_passes_on_the_reference_and_fails_on_faults():
    dist, thr = _stream_case()
    kept = rstream.greedy_exclusion(dist, thr, 32)
    assert len(kept) >= 10
    assert rstream.compare(kept, dist, thr, 32, 1e-4) == {
        "dist_gap": 0.0, "unexplained": 0.0}
    dropped = kept[:3] + kept[4:]
    assert rstream.compare(dropped, dist, thr, 32, 1e-4)["unexplained"] >= 1
    t, s, d = kept[5]
    moved = kept[:5] + [(t, s + 1, d)] + kept[6:]
    assert rstream.compare(moved, dist, thr, 32, 1e-4)["unexplained"] >= 1
    bent = kept[:5] + [(t, s, d * 1.01)] + kept[6:]
    assert rstream.compare(bent, dist, thr, 32, 1e-4)["dist_gap"] > 1e-4


def test_stream_compare_excuses_a_decision_on_the_threshold():
    dist, thr = _stream_case(10)
    kept = rstream.greedy_exclusion(dist, thr, 32)
    t, s, d = kept[0]
    thr = thr.copy()
    thr[t] = d * (1 + 1e-6)  # that hit now sits on its threshold
    kept = rstream.greedy_exclusion(dist, thr, 32)
    without = [h for h in kept if (h[0], h[1]) != (t, s)]
    assert rstream.compare(without, dist, thr, 32, 1e-4)["unexplained"] == 0


def test_window_znorm_is_per_window():
    x = np.arange(10, dtype=np.float64)[None] * 3 + 100
    z = rstream.znorm_rows(x)
    np.testing.assert_allclose(z.mean(), 0, atol=1e-6)
    np.testing.assert_allclose(z.std(), 1, atol=1e-6)
    flat = rstream.znorm_rows(np.full((1, 8), 5.0))
    assert np.all(flat == 0)


def test_generators_are_seeded():
    a = generate.rng_for(2**31 + 11, generate.DATA).standard_normal(4)
    b = generate.rng_for(2**31 + 11, generate.DATA).standard_normal(4)
    c = generate.rng_for(2**31 + 11, generate.TRAFFIC).standard_normal(4)
    d = generate.rng_for(-(2**31 + 11), generate.DATA).standard_normal(4)
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert not np.array_equal(a, d)
    rows = _walks(11, 20, 16)
    mix = generate.query_mix(generate.rng_for(1, 1), rows, 100, repeat_frac=0.3,
                             near_frac=0.4, repeat_pool=8, near_sigma=0.25)
    assert mix.shape == (100, 16) and mix.dtype == np.float32
    assert len(np.unique(mix, axis=0)) <= 100 - 30 + 8


def test_planted_stream_is_continuous_and_holds_its_plants():
    rng = np.random.default_rng(12)
    templates = generate.random_walks(rng, 2, 32)
    stream, plants = generate.planted_walk_stream(
        rng, 2000, templates, every=200, amp_range=(1.0, 1.0), noise=0.0)
    assert len(plants) == 10
    for tid, pos in plants:
        seg = stream[pos : pos + 32].astype(np.float64)
        np.testing.assert_allclose(seg - seg[0], templates[tid] - templates[tid][0],
                                   atol=1e-3)
    assert np.max(np.abs(np.diff(stream))) < 10
