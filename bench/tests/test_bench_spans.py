"""The readers of the program's own spans (``bench/spans.py`` and the
``program_span`` metrics): their sums on a hand-built window, and None
where there is nothing to read."""

from __future__ import annotations

import sys

import pytest

from bench_testing import ROOT  # noqa: F401  (puts the checkout on the path)

from bench import harness

SPAN_METRICS = [m["name"] for m in harness.load_spec()["per_layer"]
                if m["source"] == "program_span"]


class Ctx:
    def __init__(self):
        self.counters = {"t0": 100.0, "t_end": 200.0}
        self.trace, self.peaks, self.config = None, {}, {}


def _window():
    """Two host-driver blocks of 10 ms, each with 3 ms of reads (one
    nested in an LB stage), a read outside any block, one session call
    of 100 ms around 80 ms of host driver; and the same shape for the
    stream scanner."""
    from repro.obs import Span

    def sp(name, t0_ms, t1_ms, parent, sid):
        return Span(name, 100 + t0_ms / 1e3, 100 + t1_ms / 1e3, parent, 7, None, sid)

    return [
        sp("session.query", 0, 100, None, 1),
        sp("session.plan", 1, 2, 1, 2),
        sp("session.host", 10, 90, 1, 3),
        sp("session.host.block", 10, 20, 3, 4),
        sp("session.host.lb", 10, 14, 4, 5),
        sp("session.host.wait", 11, 13, 5, 6),
        sp("session.host.wait", 15, 16, 4, 7),
        sp("session.host.block", 20, 30, 3, 8),
        sp("session.host.dp", 20, 29, 8, 9),
        sp("session.host.wait", 22, 25, 9, 10),
        sp("session.host.wait", 85, 89, 3, 11),  # outside the blocks
        sp("stream.push", 300, 330, None, 20),
        sp("stream.ingest", 300, 302, 20, 21),
        sp("stream.block", 302, 312, 20, 22),
        sp("stream.wait", 303, 304, 22, 23),
        sp("stream.tally", 305, 310, 22, 24),
        sp("stream.wait", 306, 309, 24, 25),
        sp("stream.block", 312, 316, 20, 26),
        sp("stream.wait", 313, 315, 26, 27),
    ]


@pytest.fixture
def window(monkeypatch):
    from repro import obs

    asked = []

    def spans_between(t0, t1):
        asked.append((t0, t1))
        return _window()

    monkeypatch.setattr(obs, "spans_between", spans_between)
    return asked


def test_the_five_span_metrics_are_declared():
    assert sorted(SPAN_METRICS) == sorted([
        "session.self_ms", "driver.host_ms_per_block", "driver.wait_ms_per_block",
        "stream.host_ms_per_block", "stream.wait_ms_per_block"])


@pytest.mark.parametrize("metric,want", [
    ("session.self_ms", 20.0),  # 100 ms call less 80 ms of host driver
    ("driver.host_ms_per_block", 7.0),  # (20 ms - 6 ms of reads) / 2
    ("driver.wait_ms_per_block", 3.0),  # 6 ms of reads in blocks / 2
    ("stream.host_ms_per_block", 4.0),  # (14 ms - 6 ms of reads) / 2
    ("stream.wait_ms_per_block", 3.0),
])
def test_readers_on_a_hand_built_window(window, metric, want):
    got = harness.metric_module(metric).read(Ctx())
    assert got == pytest.approx(want)
    assert window == [(100.0, 200.0)]  # the window's own bounds


@pytest.mark.parametrize("metric", SPAN_METRICS)
def test_readers_give_none_when_the_ring_wrapped(monkeypatch, metric):
    from repro import obs

    def wrapped(t0, t1):
        raise obs.RingWrapped("overwritten")

    monkeypatch.setattr(obs, "spans_between", wrapped)
    assert harness.metric_module(metric).read(Ctx()) is None


@pytest.mark.parametrize("metric", SPAN_METRICS)
def test_readers_give_none_on_an_empty_window(monkeypatch, metric):
    from repro import obs

    monkeypatch.setattr(obs, "spans_between", lambda t0, t1: [])
    assert harness.metric_module(metric).read(Ctx()) is None


@pytest.mark.parametrize("metric", SPAN_METRICS)
def test_readers_give_none_without_the_programs_ring(monkeypatch, metric):
    """A program that records no spans of its own (an older checkout
    the benchmark is laid over) has no ``repro.obs``."""
    import repro

    monkeypatch.delattr(repro, "obs", raising=False)
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    assert harness.metric_module(metric).read(Ctx()) is None
