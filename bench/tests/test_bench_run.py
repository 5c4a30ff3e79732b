"""Whole runs of the benchmark on the CPU at a tiny size: the chip check,
a sound run of each cell, the control, and the faults the comparison
has to catch with the timed path broken underneath."""

from __future__ import annotations

import dataclasses
import os
import subprocess
import sys

import numpy as np
import pytest

from bench_testing import ROOT, cell_object, run_cell, tiny_root

CELLS = ["rw-cold", "ucr-stream", "rw-mixed"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(tmp_path_factory.mktemp("bench"))


def test_no_tpu_means_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "rw-cold", "--seed",
         "2147483659", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert not [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]
    assert "no TPU" in proc.stderr


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(root, cell):
    res = run_cell(root, cell, seed=2**31 + 7)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert "setup_s" in res["metrics"] and len(res["metrics"]) >= 2
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert list(res["checks"]) and all(
        set(c) == {"value", "limit"} for c in res["checks"].values())


@pytest.mark.parametrize("cell", CELLS)
def test_control_in_bfloat16_fails(root, cell):
    sess = cell_object(root, cell, seed=2**31 + 8)
    nums = sess.control("bfloat16")
    limits = sess.config["check"]["limits"]
    assert any(nums[k] > limits[k] for k in nums), nums


# ---------------------------------------------------------------- faults


def _knn_fault(monkeypatch, alter):
    """Break the host driver underneath the engine: ``alter`` rewrites
    the batch result where it is produced."""
    import repro.api.database as database

    real = database.nn_search_host

    def broken(q, db, *args, **kwargs):
        return alter(real, q, db, *args, **kwargs)

    monkeypatch.setattr(database, "nn_search_host", broken)


def test_knn_fault_altered_answer(root, monkeypatch):
    def alter(real, q, db, *a, **kw):
        res = real(q, db, *a, **kw)
        dist = res.distances.copy()
        dist[0, 0] *= 1 + 1e-3
        return dataclasses.replace(res, distances=dist)

    _knn_fault(monkeypatch, alter)
    res = run_cell(root, "rw-cold", seed=2**31 + 9)
    assert not res["correct"]


def test_knn_fault_state_unchanged(root, monkeypatch):
    first = []

    def alter(real, q, db, *a, **kw):  # every batch gets the first answer
        if not first:
            first.append(real(q, db, *a, **kw))
        return first[0]

    _knn_fault(monkeypatch, alter)
    res = run_cell(root, "rw-cold", seed=2**31 + 16)
    assert not res["correct"]


def test_knn_fault_half_the_rows_left_out(root, monkeypatch):
    def alter(real, q, db, *a, **kw):
        return real(q, db[: db.shape[0] // 2], *a, **kw)

    _knn_fault(monkeypatch, alter)
    res = run_cell(root, "rw-cold", seed=2**31 + 10)
    assert not res["correct"]


def test_knn_fault_wrong_row_named(root, monkeypatch):
    def alter(real, q, db, *a, **kw):
        res = real(q, db, *a, **kw)
        idx = res.indices.copy()
        idx[:, 0] = (idx[:, 0] + 1) % db.shape[0]
        return dataclasses.replace(res, indices=idx)

    _knn_fault(monkeypatch, alter)
    res = run_cell(root, "rw-cold", seed=2**31 + 11)
    assert not res["correct"]


def _stream_fault(monkeypatch, alter):
    """Break the stream scanner where it produces raw hits."""
    from repro.stream.subsequence import SubsequenceScanner

    real = SubsequenceScanner.process_block

    def broken(self, state, start0, n_valid):
        return alter(real(self, state, start0, n_valid), start0)

    monkeypatch.setattr(SubsequenceScanner, "process_block", broken)


def test_stream_fault_match_altered(root, monkeypatch):
    from repro.stream.subsequence import Match

    _stream_fault(monkeypatch, lambda hits, s0: [
        Match(h.tid, h.start, h.dist * 1.01) for h in hits])
    res = run_cell(root, "ucr-stream", seed=2**31 + 12)
    assert not res["correct"]


def test_stream_fault_half_the_windows_left_out(root, monkeypatch):
    _stream_fault(monkeypatch, lambda hits, s0: hits if (s0 // 32) % 2 else [])
    res = run_cell(root, "ucr-stream", seed=2**31 + 13)
    assert not res["correct"]


def test_stream_fault_state_unchanged(root, monkeypatch):
    from repro.stream.state import StreamState

    real = StreamState.push
    first = {}

    def push(self, samples):  # the ring keeps taking the first bite again
        samples = np.asarray(samples)
        head = first.setdefault(id(self), samples.copy())
        return real(self, np.resize(head, samples.shape))

    monkeypatch.setattr(StreamState, "push", push)
    res = run_cell(root, "ucr-stream", seed=2**31 + 17)
    assert not res["correct"]


def test_stream_fault_match_dropped(root, monkeypatch):
    from repro.stream.matcher import StreamMatcher

    real = StreamMatcher.poll
    state = {"dropped": 0}

    def poll(self):  # each poll loses its first finalised match
        out = real(self)
        state["dropped"] += bool(out)
        return out[1:]

    monkeypatch.setattr(StreamMatcher, "poll", poll)
    res = run_cell(root, "ucr-stream", seed=2**31 + 14)
    assert state["dropped"] >= 1
    assert not res["correct"]


def test_metric_readers_on_a_recorded_window(root):
    """The program-counter readers find their numbers in a tiny run."""
    from bench import harness

    spec = harness.load_spec(root)
    for cell in ("rw-mixed", "ucr-stream"):
        sess = cell_object(root, cell, seed=2**31 + 15)
        ctx = type("Ctx", (), {"counters": sess.counters, "trace": None,
                               "peaks": {}, "config": sess.config})()
        for m in harness.cell_metrics(spec, cell, "per_layer"):
            value = harness.metric_module(m["name"], root).read(ctx)
            if m["source"] == "device_trace":
                assert value is None  # no trace: nothing to read
            else:
                assert value is not None and np.isfinite(value), m["name"]
