"""The trace reduction: on hand-built planes with known intervals, and on
a small trace recorded on a TPU v5e (``data/small.xplane.pb``: two
harness ``session.search`` spans inside a ``bench.window`` span, each
running one dense LB stage and one DP program)."""

from __future__ import annotations

import pathlib

import pytest

from bench_testing import ROOT  # noqa: F401  (puts the checkout on the path)

from bench import trace

FIXTURE = pathlib.Path(__file__).parent / "data" / "small.xplane.pb"


class Ev:
    def __init__(self, name, start, dur):
        self.name, self.start_ns, self.duration_ns, self.stats = name, start, dur, []


class Line:
    def __init__(self, name, events):
        self.name, self.events = name, events


class Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


def _planes():
    host = Plane("/host:CPU", [
        Line("python", [Ev("bench.window", 1000, 10000),
                        Ev("session.search", 2000, 4000),
                        Ev("unrelated", 0, 20000)]),
    ])
    dev = Plane("/device:TPU:0", [
        Line("XLA Modules", [Ev("jit__dtw_pairs_block(7)", 2500, 1500),
                             Ev("jit_other(9)", 7000, 1000)]),
        Line("XLA Ops", [Ev("fusion.1", 2500, 1000), Ev("fusion.2", 3000, 1000),
                         Ev("copy", 7000, 1000), Ev("early", 0, 1500)]),
    ])
    return [host, dev]


def test_busy_idle_modules_and_gaps_from_known_intervals():
    s = trace.reduce_planes(_planes())
    assert s.window_s == pytest.approx(10000e-9)
    # busy: [1000,1500) clipped from "early", [2500,4000), [7000,8000)
    assert s.busy_s == pytest.approx(3000e-9)
    assert s.idle_share == pytest.approx(0.7)
    assert s.module_seconds("_dtw_pairs_block") == (pytest.approx(1500e-9), 1)
    assert s.op_s["jit__dtw_pairs_block/fusion.1"] == pytest.approx(1000e-9)
    assert s.op_s["jit_other/copy"] == pytest.approx(1000e-9)
    # the gaps [1500,2500) and [4000,7000) are named by session.search,
    # which covers their middles; [8000,11000) by no span but the window
    assert s.idle_by_span["session.search"] == pytest.approx(4000e-9)
    assert s.idle_by_span[trace.OUTSIDE] == pytest.approx(3000e-9)
    assert sum(s.idle_by_span.values()) == pytest.approx(s.window_s - s.busy_s)
    b = s.breakdown()
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert b["idle_gaps"][0] == ["session.search", pytest.approx(4000e-9)]


def test_missing_window_or_device_is_an_error():
    host, dev = _planes()
    with pytest.raises(ValueError, match="no 'bench.window' span"):
        trace.reduce_planes([dev])
    with pytest.raises(ValueError, match="plane"):
        trace.reduce_planes([host])


@pytest.mark.skipif(not FIXTURE.is_file(), reason="recorded trace not present")
def test_recorded_tpu_trace():
    s = trace.reduce_file(str(FIXTURE))
    assert s.chips == 1
    assert 0 < s.busy_s < s.window_s
    lb, lb_calls = s.module_seconds("_dense_stage_qblock")
    dp, dp_calls = s.module_seconds("_dtw_pairs_block")
    assert lb_calls == 2 and dp_calls == 2
    assert 0 < lb < s.window_s and 0 < dp < s.window_s
    assert sum(s.idle_by_span.values()) == pytest.approx(
        s.window_s - s.busy_s, rel=1e-9)
    assert "session.search" in s.idle_by_span
