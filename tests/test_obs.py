"""The program's span recorder (``repro.obs``) and the spans the served
paths open: nesting, self time, request ids, the ring's bound, and the
span counts against the drivers' own stats."""

from __future__ import annotations

import math
import sys
import threading
import time

import numpy as np
import pytest

from repro import obs
from repro.obs import Ring, RingWrapped, Span


def _span(name, t0, t1, parent=None, sid=0):
    return Span(name, t0, t1, parent, None, None, sid)


def _subtree(spans, root):
    """``root`` and every span opened under it."""
    ids, out = {root.id}, [root]
    for s in sorted(spans, key=lambda s: s.t0):
        if s.parent in ids and s.id not in ids:
            ids.add(s.id)
            out.append(s)
    return out


def _calls(name, fn):
    """Run ``fn`` and return its result with the spans of each ``name``
    call it made (top-level in this thread), one list per call."""
    t0 = time.perf_counter()
    out = fn()
    spans = obs.spans_between(t0, time.perf_counter())
    roots = [s for s in spans if s.name == name]
    return out, [_subtree(spans, r) for r in roots]


# ---------------------------------------------------------------- module


def test_nesting_and_parents():
    ring = Ring(64)
    with ring.span("a") as a:
        with ring.span("b", pairs=16) as b:
            with ring.span("c") as c:
                pass
        with ring.span("d") as d:
            pass
    closed = ring.spans_between(-math.inf, math.inf)
    recs = {r.name: r for r in closed}
    assert [r.name for r in closed] == ["c", "b", "d", "a"]
    assert recs["a"].parent is None
    assert recs["b"].parent == recs["d"].parent == a.id == recs["a"].id
    assert recs["c"].parent == b.id and recs["c"].id == c.id
    assert recs["d"].id == d.id
    assert recs["b"].attrs == {"pairs": 16} and recs["a"].attrs is None
    for r in recs.values():
        assert r.t0 <= r.t1 and r.request_id is None
    assert recs["a"].t0 <= recs["b"].t0 <= recs["c"].t0 <= recs["c"].t1
    assert recs["b"].t1 <= recs["d"].t0 <= recs["d"].t1 <= recs["a"].t1


def test_span_closes_on_exception():
    ring = Ring(8)
    with pytest.raises(ValueError):
        with ring.span("outer"):
            with ring.span("inner"):
                raise ValueError("boom")
    with ring.span("after") as after:
        pass
    recs = {r.name: r for r in ring.spans_between(-math.inf, math.inf)}
    assert set(recs) == {"outer", "inner", "after"}
    assert recs["after"].parent is None and after.parent is None


def test_self_time_with_overlapping_children():
    recs = [
        _span("p", 0.0, 10.0, None, 1),
        _span("c1", 1.0, 4.0, 1, 2),
        _span("c2", 3.0, 6.0, 1, 3),  # overlaps c1: [1, 6) covered once
        _span("c3", 5.0, 5.5, 1, 4),  # inside c2
        _span("c4", 9.0, 12.0, 1, 5),  # runs past the parent: [9, 10)
        _span("g", 1.5, 2.5, 2, 6),  # grandchild: c1's, not p's
    ]
    st = dict(zip((r.name for r in recs), obs.self_times(recs)))
    assert st["p"] == pytest.approx(10.0 - 5.0 - 1.0)
    assert st["c1"] == pytest.approx(3.0 - 1.0)
    assert st["c2"] == pytest.approx(3.0)
    assert st["c4"] == pytest.approx(3.0)
    assert st["g"] == pytest.approx(1.0)


def test_self_times_of_a_recorded_tree_sum_to_the_root():
    ring = Ring(64)
    with ring.span("root"):
        for _ in range(3):
            with ring.span("child"):
                with ring.span("leaf"):
                    time.sleep(0.001)
    recs = ring.spans_between(-math.inf, math.inf)
    root = next(r for r in recs if r.name == "root")
    assert sum(obs.self_times(recs)) == pytest.approx(root.seconds, rel=1e-9)


def test_request_ids_shared_in_a_thread_and_separate_across():
    seen = {}

    def work(key):
        with obs.request() as rid:
            with obs.span("test.obs.outer") as o:
                with obs.span("test.obs.inner") as i:
                    pass
        with obs.span("test.obs.after") as a:
            pass
        seen[key] = (rid, o.request_id, i.request_id, a.request_id)

    threads = [threading.Thread(target=work, args=(k,)) for k in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert not any(t.is_alive() for t in threads)
    rids = [v[0] for v in seen.values()]
    assert len(set(rids)) == 4
    for rid, outer, inner, after in seen.values():
        assert outer == inner == rid and after is None


def test_parents_are_kept_per_thread():
    """Threads opening spans into one ring at once: every inner span's
    parent is its own thread's outer span, and no append is lost."""
    ring = Ring(1 << 14)
    n_threads, n_iter = 16, 200
    pairs = [[] for _ in range(n_threads)]
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:

        def work(k):
            for _ in range(n_iter):
                with ring.span("outer") as o:
                    with ring.span("inner") as i:
                        pass
                pairs[k].append((o.id, i.id))

        threads = [
            threading.Thread(target=work, args=(k,)) for k in range(n_threads)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    recs = {r.id: r for r in ring.spans_between(-math.inf, math.inf)}
    assert len(recs) == 2 * n_threads * n_iter
    for plist in pairs:
        for oid, iid in plist:
            assert recs[iid].parent == oid and recs[oid].parent is None


def test_ring_bound_and_wrap_detection():
    ring = Ring(4)
    for k in range(4):
        ring._append(("s", float(k), k + 0.5, None, None, None, k))
    assert [r.id for r in ring.spans_between(0.0, 10.0)] == [0, 1, 2, 3]
    ring._append(("s", 4.0, 4.5, None, None, None, 4))  # overwrites id 0
    ring._append(("s", 5.0, 5.5, None, None, None, 5))  # overwrites id 1
    for t0 in (-math.inf, 0.0, 1.5):  # id 1 closed at 1.5
        with pytest.raises(RingWrapped):
            ring.spans_between(t0, 10.0)
    assert [r.id for r in ring.spans_between(1.6, 10.0)] == [2, 3, 4, 5]
    with pytest.raises(ValueError):
        Ring(0)


def test_process_ring_holds_five_windows_of_the_busiest_cell():
    # about 40k spans in a 40 s window of the stream cell (PERF.md)
    assert obs.CAPACITY >= 5 * 40_000
    assert obs.RING.capacity == obs.CAPACITY


def test_spans_between_at_the_window_edges():
    ring = Ring(16)
    for sid, (t0, t1) in enumerate(
        [(0.5, 1.5), (1.0, 2.0), (1.0, 3.0), (2.5, 3.0), (2.5, 3.5), (4.0, 5.0)]
    ):
        ring._append(("s", t0, t1, None, None, None, sid))
    # opened at or after t0 and closed at or before t1, edges included
    assert [r.id for r in ring.spans_between(1.0, 3.0)] == [1, 2, 3]
    assert [r.id for r in ring.spans_between(3.0, 3.0)] == []
    assert [r.id for r in ring.spans_between(2.5, 3.0)] == [3]
    assert [r.id for r in ring.spans_between(0.0, 10.0)] == list(range(6))


def test_spanned_keeps_the_signature_and_records_calls():
    import inspect

    @obs.spanned("test.obs.fn")
    def fn(a, b=2):
        """doc"""
        with obs.span("test.obs.body"):
            return a + b

    assert fn.__doc__ == "doc"
    assert list(inspect.signature(fn).parameters) == ["a", "b"]
    out, calls = _calls("test.obs.fn", lambda: fn(1) + fn(2, b=5))
    assert out == 10 and len(calls) == 2
    assert all([s.name for s in c] == ["test.obs.fn", "test.obs.body"] for c in calls)


# ---------------------------------------------------------------- drivers


@pytest.fixture(scope="module")
def tiny_db():
    rng = np.random.default_rng(7)
    db = np.cumsum(rng.normal(size=(200, 32)), axis=1).astype(np.float32)
    qs = np.cumsum(rng.normal(size=(5, 32)), axis=1).astype(np.float32)
    return db, qs


@pytest.mark.parametrize("early_abandon", [False, True])
def test_host_driver_spans_match_its_stats(tiny_db, early_abandon):
    from repro.core.cascade import nn_search_host, nn_search_scan

    db, qs = tiny_db
    res, calls = _calls(
        "session.host",
        lambda: nn_search_host(
            qs, db, w=3, p=1, k=3, block=32, dtw_chunk=8,
            early_abandon=early_abandon,
        ),
    )
    assert len(calls) == 1
    spans = calls[0]
    st = res.stats
    by = {}
    for s in spans:
        by.setdefault(s.name, []).append(s)
    assert all(name.startswith("session.host") for name in by)
    assert len(by["session.host.block"]) == st.blocks_total == 7
    assert len(by["session.host.dp"]) == st.blocks_dtw > 0
    assert len(by["session.host.merge"]) == st.blocks_dtw
    assert sum(s.attrs["pairs"] for s in by["session.host.dp"]) == st.dp_lane_work
    assert sum(s.attrs["useful"] for s in by["session.host.dp"]) == st.dp_lane_useful
    assert len(by["session.host.compact"]) == st.blocks_total
    assert len(by["session.host.lb"]) == st.blocks_total + st.blocks_lb2
    # one read per LB stage and per DP chunk, plus the final distances
    parents = {s.id: s.name for s in spans}
    waits = [parents[s.parent] for s in by["session.host.wait"]]
    assert sorted(set(waits)) == ["session.host", "session.host.dp", "session.host.lb"]
    assert waits.count("session.host.dp") == st.blocks_dtw
    assert waits.count("session.host") == 1
    # the spans change nothing: the host sweep still equals the scan
    ref = nn_search_scan(qs, db, w=3, p=1, k=3, block=32)
    np.testing.assert_array_equal(res.indices, ref.indices)
    np.testing.assert_array_equal(res.distances, ref.distances)


def test_session_query_holds_plan_and_host(tiny_db):
    from repro.api import Database, SearchConfig

    db, qs = tiny_db
    sess = Database.build(db, SearchConfig(w=3, p=1, k=3, block=32))
    res, calls = _calls("session.query", lambda: sess.search(qs, driver="host"))
    assert len(calls) == 1
    names = [s.name for s in calls[0]]
    assert names[0] == "session.query"
    assert names.count("session.plan") == 1 and names.count("session.host") == 1
    query = calls[0][0]
    for s in calls[0][1:]:
        if s.name in ("session.plan", "session.host"):
            assert s.parent == query.id
    assert names.count("session.host.block") == res.stats.blocks_total


def test_engine_batch_shares_one_request_id(tiny_db):
    from repro.api import Database, SearchConfig
    from repro.serve import QueryEngine

    db, qs = tiny_db
    sess = Database.build(db, SearchConfig(w=3, p=1, k=3, block=32))
    t0 = time.perf_counter()
    with QueryEngine(sess, max_batch=4, max_wait_ms=50, cache_capacity=0) as eng:
        futures = [eng.submit(q, tenant=f"t{i % 2}") for i, q in enumerate(qs)]
        answers = [f.result(timeout=120) for f in futures]
    spans = obs.spans_between(t0, time.perf_counter())
    assert all(a.indices.shape == (3,) for a in answers)
    batches = [s for s in spans if s.name == "engine.batch"]
    executed = [b for b in batches if any(s.parent == b.id for s in spans)]
    assert len(executed) >= 2  # 5 queries at 4 lanes a batch
    rids = [b.request_id for b in executed]
    assert None not in rids and len(set(rids)) == len(rids)
    for b in executed:
        tree = _subtree(spans, b)
        names = {s.name for s in tree}
        assert {"session.query", "session.plan", "engine.fanout"} <= names
        assert {s.request_id for s in tree} == {b.request_id}


def test_stream_scanner_spans_match_its_stats():
    from repro.stream import StreamMatcher

    rng = np.random.default_rng(3)
    templates = np.cumsum(rng.normal(size=(3, 24)), axis=1)
    stream = np.cumsum(rng.normal(size=700))
    m = StreamMatcher(templates, 3, 6.0, p=2, znorm=True, block=16)
    t0 = time.perf_counter()
    for lo in range(0, stream.size, 128):
        m.feed(stream[lo : lo + 128])
    spans = obs.spans_between(t0, time.perf_counter())
    pushes = [s for s in spans if s.name == "stream.push"]
    mine = [s for p in pushes for s in _subtree(spans, p)]
    by = {}
    for s in mine:
        by.setdefault(s.name, []).append(s)
    st = m.stats
    assert len(pushes) == 6
    assert len(by["stream.block"]) == st.blocks_total > 0
    assert sum(s.attrs["windows"] for s in by["stream.block"]) == st.n_windows[0]
    for leaf in ("stream.windows", "stream.prefilter", "stream.match", "stream.tally"):
        assert len(by[leaf]) == st.blocks_total, leaf
    parents = {s.id: s.name for s in mine}
    assert {parents[s.parent] for s in by["stream.wait"]} == {
        "stream.block", "stream.tally"}
    # the distances, one mask per stage boundary, four scalar counts
    reads = 1 + (len(st.stage_names) + 1) + 4
    assert len(by["stream.wait"]) == st.blocks_total * reads
    assert len(by["stream.ingest"]) >= len(pushes)
    resolves = [s for s in spans if s.name == "stream.resolve"]
    assert len(resolves) == len(pushes)
    assert all(not s.name.startswith(("bench.", "stream.feed")) for s in spans)
