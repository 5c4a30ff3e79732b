"""Facade parity + artifact-cache regression tests (ISSUE 5).

The session facade must add *zero* numeric surface of its own: for
fixed inputs, ``Database.search`` / ``classify`` / ``stream`` return
bit-identical values, indices and stage counters to the legacy entry
points, across p in {1, 2, inf}, indexed and not, and after a
``save`` -> ``load`` round trip.  Build-once artifacts must actually be
built once: a second ``search`` performs zero database-side envelope
recomputation.
"""

import math
import os

import numpy as np
import pytest

import repro.api.database as api_db
import repro.core.cascade as cascade_mod
from repro.api import Database, Plan, SearchConfig, plan_search
from repro.core.cascade import (
    nn_search_host,
    nn_search_indexed,
    nn_search_scan,
)
from repro.core.classify import nn_classify
from repro.data.synthetic import planted_stream, random_walks, template_bank
from repro.stream import StreamMatcher

from helpers import run_in_subprocess

RNG = np.random.default_rng(7)
N_DB, N, W = 96, 64, 6
P_VALUES = [1, 2, math.inf]


@pytest.fixture(scope="module")
def problem():
    db = random_walks(RNG, N_DB, N)
    near = db[RNG.integers(0, N_DB, 3)] + RNG.normal(
        scale=0.4, size=(3, N)
    ).astype(np.float32)
    far = random_walks(RNG, 2, N)
    return db, np.concatenate([near, far])


def assert_same_result(got, want):
    np.testing.assert_array_equal(got.distances, want.distances)
    np.testing.assert_array_equal(got.indices, want.indices)
    assert got.stats == want.stats
    if hasattr(got, "per_query"):
        assert got.per_query == want.per_query


# ----------------------------------------------------------- search parity


@pytest.mark.parametrize("p", P_VALUES)
@pytest.mark.parametrize("driver,legacy", [
    ("scan", nn_search_scan),
    ("host", nn_search_host),
])
def test_search_parity_unindexed(problem, p, driver, legacy):
    data, qs = problem
    db = Database.build(data, SearchConfig(w=W, p=p, k=3))
    got = db.search(qs, driver=driver)
    want = legacy(qs, data, w=W, p=p, k=3, block=32)
    assert_same_result(got, want)
    # single query keeps the scalar SearchResult shape
    got1 = db.search(qs[0], driver=driver)
    want1 = legacy(qs[0], data, w=W, p=p, k=3, block=32)
    assert_same_result(got1, want1)


@pytest.mark.parametrize("p", P_VALUES)
def test_search_parity_indexed(problem, p):
    data, qs = problem
    db = Database.build(data, SearchConfig(w=W, p=p), index=True, n_refs=8)
    got = db.search(qs)  # planner must route through the index
    assert db.plan(qs).driver == "indexed"
    want = nn_search_indexed(qs, data, db.index, k=1, block=32)
    assert_same_result(got, want)


@pytest.mark.parametrize("indexed", [False, True])
@pytest.mark.parametrize("p", P_VALUES)
def test_save_load_round_trip(problem, tmp_path, p, indexed):
    data, qs = problem
    db = Database.build(
        data, SearchConfig(w=W, p=p, k=2), index=indexed, n_refs=8
    )
    before = db.search(qs)
    path = db.save(os.path.join(tmp_path, "session"))
    assert path.endswith(".npz")
    db2 = Database.load(path)
    assert db2.config == db.config and db2.w == db.w
    np.testing.assert_array_equal(db2.upper, db.upper)
    np.testing.assert_array_equal(db2.lower, db.lower)
    np.testing.assert_array_equal(db2.row_sums, db.row_sums)
    assert (db2.index is None) == (not indexed)
    assert_same_result(db2.search(qs), before)


def test_load_rejects_unknown_bundle_version(problem, tmp_path):
    data, _ = problem
    db = Database.build(data, SearchConfig(w=W))
    path = db.save(os.path.join(tmp_path, "session"))
    with np.load(path) as z:
        arrays = {k: z[k] for k in z.files}
    arrays["bundle_format_version"] = np.int64(99)
    np.savez_compressed(path, **arrays)
    with pytest.raises(ValueError, match="bundle format v99"):
        Database.load(path)


def test_topk_override(problem):
    data, qs = problem
    db = Database.build(data, SearchConfig(w=W, k=1))
    got = db.topk(qs, k=4)
    want = nn_search_scan(qs, data, w=W, p=1, k=4)
    assert_same_result(got, want)


def test_method_override_parity(problem):
    """The stage pipeline is a per-call knob: no rebuild, same artifacts,
    bit-identical to the legacy call with that method."""
    data, qs = problem
    db = Database.build(data, SearchConfig(w=W))  # config: lb_improved
    got = db.search(qs, driver="scan", method="lb_keogh")
    want = nn_search_scan(qs, data, w=W, p=1, k=1, method="lb_keogh")
    assert_same_result(got, want)
    # planner sees the override too: method="full" routes to the scan
    assert db.plan(qs, method="full").driver == "scan"
    assert db.plan(qs, method="full").stages == ("full",)
    # and the config object itself stays untouched
    assert db.config.method == "lb_improved"


def test_znorm_search_matches_manually_normalized_legacy(problem):
    from repro.stream import znorm_series

    data, qs = problem
    db = Database.build(data, SearchConfig(w=W, p=2, znorm=True))
    got = db.search(qs, driver="scan")
    data_z = np.stack([znorm_series(r) for r in data])
    qs_z = np.stack([znorm_series(q) for q in qs])
    want = nn_search_scan(qs_z, data_z, w=W, p=2, k=1)
    assert_same_result(got, want)


def test_query_shape_errors(problem):
    data, _ = problem
    db = Database.build(data, SearchConfig(w=W))
    with pytest.raises(ValueError, match="query length 32 != expected"):
        db.search(np.zeros(32, np.float32))
    with pytest.raises(ValueError, match=r"one \(n,\) series or a \(Q, n\)"):
        db.search(np.zeros((2, 3, 4), np.float32))


# ------------------------------------------- build-once artifact regression


def test_second_search_recomputes_no_database_envelopes(
    problem, monkeypatch
):
    """ISSUE 5 satellite: database-side envelopes are a build artifact.

    ``envelope_batch_mv`` (the channel-aware constructor every driver
    routes through since the mv tier) is monkeypatched with a
    shape-recording counter in both the facade module (build-time
    calls) and the cascade module (query-time calls).  Build must
    compute the (N_DB, n) envelopes exactly once; every later
    ``search`` may only ever compute query-shaped envelopes — the ones
    that genuinely depend on the query.
    """
    data, qs = problem
    calls: list[tuple[int, ...]] = []
    real_mv = api_db.envelope_batch_mv

    def counting_mv(xs, w, d=1):
        calls.append(tuple(xs.shape))
        return real_mv(xs, w, d)

    monkeypatch.setattr(api_db, "envelope_batch_mv", counting_mv)
    monkeypatch.setattr(cascade_mod, "envelope_batch_mv", counting_mv)

    db = Database.build(data, SearchConfig(w=W))
    db_shape = (N_DB, N)
    assert calls.count(db_shape) == 1  # built exactly once

    # host driver calls envelope_batch at the python level per search,
    # so query-side laziness is observable through the patch
    db.search(qs, driver="host")
    first = list(calls)
    db.search(qs, driver="host")
    new = calls[len(first):]
    assert calls.count(db_shape) == 1, (
        f"database-side envelopes recomputed after build: {calls}"
    )
    assert new and all(s == (len(qs), N) for s in new), new


def test_device_array_uploaded_once(problem):
    data, _ = problem
    db = Database.build(data, SearchConfig(w=W))
    assert db._db_j is db._db_j  # cached attribute, not a property rebuild
    a = db._db_j
    db.search(data[0])
    assert db._db_j is a


def test_device_copy_is_lazy(problem):
    """build leaves the rows on the host; the first single-device search
    uploads them, once."""
    data, qs = problem
    db = Database.build(data, SearchConfig(w=W))
    assert db._db_dev is None
    db.search(qs, driver="scan")
    assert db._db_dev is not None


def test_build_envelopes_chunked_bit_identical(problem, monkeypatch):
    """Enveloping the database a chunk of rows at a time changes no
    value: rows are enveloped independently."""
    data, _ = problem
    whole = Database.build(data, SearchConfig(w=W))
    monkeypatch.setattr(api_db, "ENVELOPE_CHUNK_ROWS", 7)  # ragged tail
    chunked = Database.build(data, SearchConfig(w=W))
    np.testing.assert_array_equal(chunked.upper, whole.upper)
    np.testing.assert_array_equal(chunked.lower, whole.lower)
    u, l = api_db.envelope_batch_mv(np.asarray(data), whole.w, 1)
    np.testing.assert_array_equal(whole.upper, np.asarray(u))
    np.testing.assert_array_equal(whole.lower, np.asarray(l))


def test_powered_norm_artifacts(problem):
    data, _ = problem
    db = Database.build(data, SearchConfig(w=W))
    x64 = np.asarray(data, np.float64)
    np.testing.assert_allclose(db.row_sums, x64.sum(axis=1))
    np.testing.assert_allclose(db.row_sumsq, (x64**2).sum(axis=1))
    mean, std = db.row_mean_std()  # O(1) consumer of the cached norms
    np.testing.assert_allclose(mean, x64.mean(axis=1))
    np.testing.assert_allclose(std, x64.std(axis=1), rtol=1e-6)
    u, l = db.envelopes
    assert u.shape == data.shape and l.shape == data.shape
    assert (u >= data).all() and (l <= data).all()


# ---------------------------------------------------------------- classify


def test_classify_parity(problem):
    data, qs = problem
    labels = np.arange(N_DB) % 3
    db = Database.build(data, SearchConfig(w=W, p=2))
    got = db.classify(labels, qs)
    want = [nn_classify(q, data, labels, w=W, p=2) for q in qs]
    assert list(got) == want
    assert db.classify(labels, qs[0]) == want[0]  # scalar form


def test_classify_label_shape_error(problem):
    data, qs = problem
    db = Database.build(data, SearchConfig(w=W))
    with pytest.raises(ValueError, match="one label per database row"):
        db.classify(np.arange(5), qs)


# ------------------------------------------------------------------ stream


STREAM_N = 40
TEMPLATES = template_bank(STREAM_N, kinds=("sine", "gaussian"))
STREAM, _PLANTS = planted_stream(
    np.random.default_rng(123), 420, TEMPLATES, 3, noise_level=0.08
)


@pytest.mark.parametrize("znorm", [False, True])
@pytest.mark.parametrize("p", P_VALUES)
def test_stream_parity(p, znorm):
    thr = 2.5 if not znorm else 4.0
    cfg = SearchConfig(w=4, p=p, block=16, znorm=znorm)
    db = Database.build(TEMPLATES, cfg)
    got = db.stream(threshold=thr, hop=2)  # db rows as the template bank
    want = StreamMatcher(
        TEMPLATES, 4, thr, p=p, hop=2, znorm=znorm, block=16
    )
    for m in (got, want):
        m.push(STREAM)
        m.flush()
    assert got.matches() == want.matches()
    np.testing.assert_array_equal(got.stats.env_pruned, want.stats.env_pruned)
    np.testing.assert_array_equal(got.stats.full_dtw, want.stats.full_dtw)


def test_stream_reuses_cached_envelopes(monkeypatch):
    """templates=None must hand the build-time envelopes to the scanner
    instead of recomputing them (and they must be the bit-same arrays)."""
    import repro.stream.subsequence as subseq_mod

    db = Database.build(TEMPLATES, SearchConfig(w=4, block=16))

    def boom(*a, **k):  # scanner must not build envelopes at all
        raise AssertionError("scanner recomputed template envelopes")

    monkeypatch.setattr(subseq_mod, "envelope_batch_mv", boom)
    m = db.stream(threshold=2.5, hop=2)
    np.testing.assert_array_equal(np.asarray(m.scanner._u_j), db.upper)
    np.testing.assert_array_equal(np.asarray(m.scanner._l_j), db.lower)


def test_stream_rejects_unsound_prebuilt_envelopes():
    """Envelopes that don't contain the templates (wrong band /
    normalization) would silently prune true matches — refused loudly."""
    too_tight = (TEMPLATES - 0.5, TEMPLATES + 0.5)  # u < t, l > t
    with pytest.raises(ValueError, match="do not contain"):
        StreamMatcher(TEMPLATES, 4, 2.5, block=16, envelopes=too_tight)
    wrong_shape = (TEMPLATES[:1], TEMPLATES[:1])
    with pytest.raises(ValueError, match="do not match the template bank"):
        StreamMatcher(TEMPLATES, 4, 2.5, block=16, envelopes=wrong_shape)


def test_stream_explicit_templates_matches_legacy():
    other = template_bank(STREAM_N, kinds=("cosine",))
    db = Database.build(TEMPLATES, SearchConfig(w=4, p=2, block=16))
    got = db.stream(other, threshold=3.0, hop=2)
    want = StreamMatcher(other, 4, 3.0, p=2, hop=2, block=16)
    for m in (got, want):
        m.push(STREAM)
        m.flush()
    assert got.matches() == want.matches()


# ----------------------------------------------------------------- planner


def test_plan_routing_rules():
    cfg = SearchConfig()
    assert plan_search(cfg, 100, 1, has_index=True, has_mesh=True).driver == "indexed"
    assert plan_search(cfg, 100, 1, has_index=False, has_mesh=True).driver == "sharded"
    assert plan_search(cfg, 100, 1, has_index=False, has_mesh=False).driver == "scan"
    assert plan_search(cfg, 5000, 1, has_index=False, has_mesh=False).driver == "host"
    full = SearchConfig(method="full")
    assert plan_search(full, 5000, 1, has_index=False, has_mesh=False).driver == "scan"


def test_plan_explain_mentions_driver_and_stages(problem):
    data, qs = problem
    db = Database.build(data, SearchConfig(w=W))
    plan = db.plan(qs)
    assert isinstance(plan, Plan)
    text = plan.explain()
    assert plan.driver in text and "lb_keogh -> lb_improved -> full" in text
    assert "because:" in text


def test_plan_override_errors(problem):
    data, qs = problem
    db = Database.build(data, SearchConfig(w=W))
    with pytest.raises(ValueError, match="no stage-0 index is built"):
        db.plan(qs, driver="indexed")
    with pytest.raises(ValueError, match="no mesh is attached"):
        db.plan(qs, driver="sharded")
    with pytest.raises(ValueError, match="driver='warp' unknown"):
        db.plan(qs, driver="warp")


# --------------------------------------------- calibration-cache regression


def test_legacy_bundle_calibrates_once_across_plans(
    problem, tmp_path, monkeypatch
):
    """ISSUE 8 satellite: a legacy bundle (no ``cal_*`` keys) must pay
    the lazy calibration sweep exactly once per session, and
    ``method="auto"`` must memoize the cascade choice per k — repeated
    ``plan()`` / ``search()`` calls may not re-run either."""
    data, qs = problem
    db0 = Database.build(data, SearchConfig(w=W, method="auto"))
    path = db0.save(os.path.join(tmp_path, "session"))
    with np.load(path, allow_pickle=False) as z:
        arrays = {k: z[k] for k in z.files if not k.startswith("cal_")}
    np.savez_compressed(path, **arrays)

    calibrate_calls, choose_calls = [], []
    real_cal, real_choose = api_db.calibrate, api_db.choose_cascade

    def counting_cal(*a, **kw):
        calibrate_calls.append(1)
        return real_cal(*a, **kw)

    def counting_choose(cal, *, k, **kw):
        choose_calls.append(k)
        return real_choose(cal, k=k, **kw)

    monkeypatch.setattr(api_db, "calibrate", counting_cal)
    monkeypatch.setattr(api_db, "choose_cascade", counting_choose)

    db = Database.load(path)
    assert db._calibration is None  # legacy bundle: lazy
    assert not calibrate_calls

    for _ in range(3):
        db.plan(qs)
    db.search(qs)
    db.plan(qs, k=3)
    db.search(qs, k=3)
    assert len(calibrate_calls) == 1, (
        f"legacy-bundle calibration ran {len(calibrate_calls)}x"
    )
    assert sorted(set(choose_calls)) == sorted(choose_calls), (
        f"cascade re-chosen for an already-planned k: {choose_calls}"
    )
    assert set(choose_calls) == {1, 3}


# ----------------------------------------------------------------- sharded


def test_sharded_facade_parity_subprocess():
    run_in_subprocess(
        r"""
import numpy as np, jax
from jax.sharding import Mesh
from repro.api import Database, SearchConfig
from repro.core.distributed import pad_database, sharded_nn_search
from repro.data.synthetic import random_walks

rng = np.random.default_rng(0)
data = random_walks(rng, 120, 64)
qs = random_walks(rng, 4, 64)
mesh = Mesh(np.array(jax.devices()).reshape(2, 4), ("data", "model"))
db = Database.build(data, SearchConfig(w=6, p=1, k=2, block=8))
db.use_mesh(mesh, sync_every=2)
assert db.plan(qs).driver == "sharded"
got = db.search(qs)
dbp, _ = pad_database(data, mesh, block=8)
want = sharded_nn_search(qs, dbp, mesh, w=6, p=1, k=2, block=8, sync_every=2)
assert np.array_equal(got.distances, want.distances)
assert np.array_equal(got.indices, want.indices)
assert got.stats == want.stats
"""
    )


def test_use_mesh_places_one_shard_per_device_subprocess():
    """With a mesh attached every device holds its own shard and no
    device holds the whole database; an explicit single-device driver
    uploads a copy again and agrees with the sharded sweep."""
    run_in_subprocess(
        r"""
import numpy as np, jax
from repro.api import Database, SearchConfig
from repro.data.synthetic import random_walks
from repro.launch.mesh import make_host_mesh

rng = np.random.default_rng(1)
data = random_walks(rng, 256, 64)
qs = random_walks(rng, 3, 64)
mesh = make_host_mesh()
assert mesh.axis_types == (jax.sharding.AxisType.Auto,) * 2, mesh.axis_types
db = Database.build(data, SearchConfig(w=6, k=2, block=8))
db.search(qs, driver="scan")
assert db._db_dev is not None
db.use_mesh(mesh)
assert db._db_dev is None
shards = db._db_sharded.addressable_shards
assert len({s.device for s in shards}) == 4
assert all(s.data.shape == (64, 64) for s in shards), [s.data.shape for s in shards]
got = db.search(qs)
want = db.search(qs, driver="scan")
assert np.array_equal(got.distances, want.distances)
assert np.array_equal(got.indices, want.indices)
print("PLACEMENT OK")
""",
        n_devices=4,
    )
