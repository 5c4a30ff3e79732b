"""Plain z-normalised subsequence matching, and the comparison that
decides ``correct`` for the stream cells.

The reference z-normalises every window of the stream on its own (its
own mean and standard deviation, in float64), computes its DTW to every
z-normalised template, keeps the windows at or under each template's
threshold, and then applies trivial-match exclusion by the greedy rule
the service documents: in ascending order of (distance, start,
template), a hit is kept unless a kept hit of the same template starts
fewer than ``exclusion`` samples away.
"""

from __future__ import annotations

import sys

import numpy as np

from bench.reference.dtw import cross_distances

#: standard-deviation floor of z-normalisation: flat windows become 0
STD_EPS = 1e-8


def znorm_rows(x: np.ndarray) -> np.ndarray:
    """Each row minus its mean over its standard deviation, in float64,
    returned as float32."""
    x = np.asarray(x, np.float64)
    mean = x.mean(axis=1, keepdims=True)
    std = np.maximum(x.std(axis=1, keepdims=True), STD_EPS)
    return ((x - mean) / std).astype(np.float32)


def window_distances(
    stream: np.ndarray,
    templates: np.ndarray,
    w: int,
    p: int,
    *,
    dtype: str = "float32",
    windows_per_call: int = 1 << 14,
) -> np.ndarray:
    """(T, W) DTW distance of every z-normalised window (hop 1) of
    ``stream`` to every z-normalised template."""
    templates = np.asarray(templates, np.float64)
    t_count, n = templates.shape
    tz = znorm_rows(templates)
    s64 = np.asarray(stream, np.float64)
    n_win = s64.size - n + 1
    out = np.empty((t_count, max(n_win, 0)), np.float64)
    for lo in range(0, n_win, windows_per_call):
        hi = min(n_win, lo + windows_per_call)
        wins = np.lib.stride_tricks.sliding_window_view(s64[lo : hi + n - 1], n)
        out[:, lo:hi] = cross_distances(
            tz,
            znorm_rows(wins),
            w,
            p,
            dtype=dtype,
            pairs_per_call=t_count * windows_per_call,
        )
    return out


def greedy_exclusion(
    dist: np.ndarray, threshold: np.ndarray, exclusion: int
) -> list[tuple[int, int, float]]:
    """Hits (distance at or under the template's threshold) after
    trivial-match exclusion, as ``(template, start, distance)``."""
    tids, starts = np.nonzero(dist <= np.asarray(threshold)[:, None])
    dists = dist[tids, starts]
    order = np.lexsort((tids, starts, dists))
    blocked = np.zeros(dist.shape, bool)
    kept = []
    for o in order:
        t, s = int(tids[o]), int(starts[o])
        if blocked[t, s]:
            continue
        kept.append((t, s, float(dists[o])))
        blocked[t, max(0, s - exclusion + 1) : s + exclusion] = True
    return kept


def _ambiguous(
    dist: np.ndarray,
    threshold: np.ndarray,
    accepted: list[tuple[int, int, float]],
    exclusion: int,
    tol: float,
) -> dict[int, np.ndarray]:
    """Window starts, per template, at which a decision may rightly go
    either way when two computations of one distance differ by up to
    ``tol`` (relative): a window within ``tol`` of its threshold, and an
    accepted hit that another hit in its exclusion zone ties within
    ``tol``."""
    thr = np.asarray(threshold, np.float64)[:, None]
    t_b, s_b = np.nonzero(np.abs(dist - thr) <= tol * thr)
    anchors: dict[int, list[int]] = {}
    for t, s in zip(t_b.tolist(), s_b.tolist()):
        anchors.setdefault(t, []).append(s)
    for t, s, d in accepted:
        lo, hi = max(0, s - exclusion + 1), min(dist.shape[1], s + exclusion)
        zone = dist[t, lo:hi]
        near = (zone <= thr[t, 0] * (1 + tol)) & (np.abs(zone - d) <= tol * d)
        near[s - lo] = False
        if near.any():
            anchors.setdefault(t, []).extend([s] + (lo + np.nonzero(near)[0]).tolist())
    return {t: np.sort(np.asarray(v)) for t, v in anchors.items()}


def compare(
    got: list[tuple[int, int, float]],
    dist: np.ndarray,
    threshold: np.ndarray,
    exclusion: int,
    tol: float,
) -> dict[str, float]:
    """The numbers compared for a stream's matches.

    * ``dist_gap``: the widest gap between a reported match's distance
      and the reference's distance of that window, relative to the
      template's threshold (the scale every decision is made on).
    * ``unexplained``: reported matches the reference does not keep,
      plus kept matches that were not reported, leaving out those within
      two exclusion zones of a decision that may go either way (see
      ``_ambiguous``).
    """
    t_count, n_win = dist.shape
    gap = 0.0
    got_keys = set()
    for t, s, d in got:
        got_keys.add((int(t), int(s)))
        if 0 <= t < t_count and 0 <= s < n_win:
            ref = dist[t, s]
            gap = max(gap, abs(float(d) - ref) / max(float(threshold[t]), 1e-30))
        else:
            gap = float("inf")
    accepted = greedy_exclusion(dist, threshold, exclusion)
    want_keys = {(t, s) for t, s, _ in accepted}
    anchors = _ambiguous(dist, threshold, accepted, exclusion, tol)
    unexplained = 0
    for t, s in got_keys ^ want_keys:
        a = anchors.get(t)
        if a is not None and a.size:
            i = np.searchsorted(a, s)
            near = [a[j] for j in (i - 1, i) if 0 <= j < a.size]
            if any(abs(int(x) - s) < 2 * exclusion for x in near):
                continue
        unexplained += 1
    print(
        f"stream check: {len(got_keys)} matches reported, {len(want_keys)} "
        f"kept by the reference, {sum(map(len, anchors.values()))} "
        f"ambiguous windows, {unexplained} differences unexplained",
        file=sys.stderr,
    )
    return {"dist_gap": gap, "unexplained": float(unexplained)}
