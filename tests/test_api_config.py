"""SearchConfig validation: every bad knob fails loudly and actionably.

The session facade front-loads validation so a misconfigured search
dies at config/build time with a message saying what to change — not
deep inside a jitted cascade with a shape error (ISSUE 5 satellite).
"""

import dataclasses
import math

import numpy as np
import pytest

from repro.api import Database, SearchConfig


def test_defaults_are_valid():
    cfg = SearchConfig()
    assert (cfg.w, cfg.p, cfg.k, cfg.block) == (0, 1, 1, 32)
    assert cfg.method == "lb_improved"
    assert cfg.precision == "float32"


@pytest.mark.parametrize("p", [1, 1.0, 2, 2.0, math.inf, np.inf, "inf"])
def test_p_normalization(p):
    got = SearchConfig(p=float(p) if p != "inf" else math.inf).p
    if math.isinf(float(got)):
        assert got == math.inf
    else:
        assert isinstance(got, int)


@pytest.mark.parametrize("p", [4, 0.5, 0, -1, 3])
def test_p_unsupported(p):
    with pytest.raises(ValueError, match=r"p=.*\{1, 2, inf\}"):
        SearchConfig(p=p)


def test_p_not_a_number():
    with pytest.raises(ValueError, match="not a norm order"):
        SearchConfig(p="euclidean")


def test_negative_w():
    with pytest.raises(ValueError, match="w=-3 is negative"):
        SearchConfig(w=-3)


def test_w_geq_n_rejected_at_build():
    data = np.random.default_rng(0).normal(size=(8, 32)).astype(np.float32)
    with pytest.raises(ValueError, match=r"w=32 >= series length n=32"):
        Database.build(data, SearchConfig(w=32))
    with pytest.raises(ValueError, match=r"w=100 >= series length n=32"):
        Database.build(data, SearchConfig(w=100))


def test_w_zero_resolves_to_paper_default():
    assert SearchConfig(w=0).resolve_w(120) == 12
    assert SearchConfig(w=0).resolve_w(5) == 1  # floor at 1
    assert SearchConfig(w=7).resolve_w(120) == 7


def test_k_nonpositive():
    with pytest.raises(ValueError, match="k=0 must be >= 1"):
        SearchConfig(k=0)


def test_k_gt_db_size_rejected_at_build():
    data = np.random.default_rng(0).normal(size=(8, 32)).astype(np.float32)
    with pytest.raises(ValueError, match=r"k=9 > database size 8"):
        Database.build(data, SearchConfig(k=9))


def test_k_gt_db_size_rejected_at_search():
    data = np.random.default_rng(0).normal(size=(8, 32)).astype(np.float32)
    db = Database.build(data, SearchConfig(w=3))
    with pytest.raises(ValueError, match=r"k=20 > database size 8"):
        db.topk(data[0], k=20)


@pytest.mark.parametrize("block", [0, -16])
def test_block_nonpositive(block):
    with pytest.raises(ValueError, match=f"block={block} must be a positive"):
        SearchConfig(block=block)


def test_unknown_method():
    with pytest.raises(ValueError, match="method='lb_magic' unknown"):
        SearchConfig(method="lb_magic")


def test_unknown_precision():
    with pytest.raises(ValueError, match="precision='fp16' unsupported"):
        SearchConfig(precision="fp16")


def test_float64_requires_x64_at_build_and_load(tmp_path):
    import jax

    if jax.config.jax_enable_x64:
        pytest.skip("x64 enabled in this environment")
    data = np.random.default_rng(0).normal(size=(8, 32)).astype(np.float32)
    with pytest.raises(ValueError, match="needs JAX x64"):
        Database.build(data, SearchConfig(w=3, precision="float64"))
    # a float64 bundle (e.g. saved from an x64 process) must refuse to
    # load into an x64-off process instead of silently downcasting
    db = Database.build(data, SearchConfig(w=3))
    path = db.save(str(tmp_path / "sess"))
    arrays = dict(np.load(path))
    cfg64 = SearchConfig(w=3, precision="float64")
    arrays["config_json"] = np.str_(cfg64.to_json())
    np.savez_compressed(path, **arrays)
    with pytest.raises(ValueError, match="needs JAX x64"):
        Database.load(path)


def test_float64_session_serves_every_driver_exactly_subprocess():
    """Under x64 a float64 session answers through the host, scan and
    sharded drivers alike, bit for bit, and matches the float64 oracle
    (the scan and sharded sweeps once built int64 candidate ids under
    x64 and refused to trace)."""
    from helpers import run_in_subprocess

    run_in_subprocess(
        r"""
import numpy as np
from repro.api import Database, SearchConfig
from repro.core.dtw import dtw_reference
from repro.data.synthetic import random_walks
from repro.launch.mesh import make_host_mesh

rng = np.random.default_rng(0)
data = random_walks(rng, 1100, 48)
qs = random_walks(rng, 3, 48)
db = Database.build(data, SearchConfig(precision="float64", k=3))
host = db.search(qs, driver="host")
scan = db.search(qs, driver="scan")
db.use_mesh(make_host_mesh())
sharded = db.search(qs)
assert host.distances.dtype == np.float64
for got in (scan, sharded):
    assert np.array_equal(got.distances, host.distances)
    assert np.array_equal(got.indices, host.indices)
pq = db.prepare_queries(qs)
for i in range(3):
    for d, j in zip(host.distances[i], host.indices[i]):
        ref = dtw_reference(pq[i], db.data[j], db.w)
        assert abs(d - ref) <= 1e-9 * ref, (d, ref)
print("F64 OK")
""",
        n_devices=4,
        env_extra={"JAX_ENABLE_X64": "1"},
    )


def test_config_is_frozen():
    cfg = SearchConfig()
    with pytest.raises(dataclasses.FrozenInstanceError):
        cfg.k = 5


@pytest.mark.parametrize("p", [1, 2, math.inf])
def test_json_round_trip(p):
    cfg = SearchConfig(w=9, p=p, k=3, block=64, method="lb_keogh", znorm=True)
    assert SearchConfig.from_json(cfg.to_json()) == cfg
