"""The stream cell's thresholds: a fixed alarm share of windows spread
over the whole stream, so that the seed no longer sets how many windows
fall under them (on the CPU, at the cell's widths with a small bank)."""

from __future__ import annotations

import numpy as np
import pytest

from bench_testing import ROOT  # noqa: F401  (puts the checkout on the path)

from bench import generate
from bench.reference import stream as rstream
from bench.reference.dtw import cross_distances
from bench.services.stream import calibrate_thresholds

N, W, P = 128, 6, 2
TEMPLATES = 4
STREAM = 200_000
FED = 10_000
WINDOWS = 1024
SHARE = 0.10
SEEDS = [2**31 + 101 + i for i in range(6)]


def head_rule(templates, stream, *, windows=64, stride=64, frac=0.5):
    """The rule the cell used before: ``frac`` times the median distance
    of ``windows`` head windows, ``stride`` samples apart."""
    wins = np.stack([stream[s : s + N] for s in np.arange(windows) * stride])
    dist = cross_distances(
        rstream.znorm_rows(templates), rstream.znorm_rows(wins), W, P)
    return frac * np.median(dist, axis=1)


@pytest.fixture(scope="module")
def runs():
    """Per seed: the templates, the stream, both rules' thresholds and the
    (template, window) distances over the first ``FED`` windows."""
    out = {}
    for seed in SEEDS:
        templates = generate.random_walks(
            generate.rng_for(seed, generate.DATA), TEMPLATES, N)
        stream, _ = generate.planted_walk_stream(
            generate.rng_for(seed, generate.TRAFFIC), STREAM, templates,
            every=2000, amp_range=(0.8, 1.2), noise=0.05)
        out[seed] = {
            "templates": templates,
            "stream": stream,
            "share": calibrate_thresholds(
                templates, stream, W, P, windows=WINDOWS, alarm_share=SHARE),
            "head": head_rule(templates, stream),
            "fed": rstream.window_distances(stream[: FED + N - 1], templates, W, P),
        }
    return out


@pytest.mark.parametrize("seed", SEEDS)
def test_each_template_alarms_on_its_share_of_calibration_windows(runs, seed):
    run = runs[seed]
    starts = np.linspace(0, STREAM - N, WINDOWS).round().astype(int)
    assert starts[-1] + N == STREAM and np.all(np.diff(starts) > N)
    wins = np.stack([run["stream"][s : s + N] for s in starts])
    dist = cross_distances(rstream.znorm_rows(run["templates"]),
                           rstream.znorm_rows(wins), W, P)
    under = (dist <= run["share"][:, None]).sum(axis=1)
    assert np.all(np.abs(under - SHARE * WINDOWS) <= 1), under


def test_share_rule_steadies_the_hit_count_across_seeds(runs):
    def hit_range(rule):
        hits = [int((r["fed"] <= r[rule][:, None]).sum()) for r in runs.values()]
        return max(hits) - min(hits), hits

    share_range, share_hits = hit_range("share")
    head_range, head_hits = hit_range("head")
    assert share_range <= head_range / 2, (share_hits, head_hits)


def test_calibration_refuses_a_stream_with_too_few_windows():
    templates = generate.random_walks(np.random.default_rng(0), 2, N)
    with pytest.raises(ValueError, match="distinct windows"):
        calibrate_thresholds(templates, np.zeros(N + 10, np.float32), W, P,
                             windows=64, alarm_share=SHARE)
