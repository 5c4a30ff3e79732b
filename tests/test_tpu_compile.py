"""Compile the served path and the Pallas kernels for a described TPU v5e.

Nothing runs here.  Each test lowers and compiles at deployment widths
(the paper's n=1000, w=n//10, a 262,144-row database) for a ``v5e:2x2``
topology that the installed TPU compiler describes without a chip, so a
program the chip's compiler would refuse fails in CI instead of on the
chip.  The topology is described inside a module fixture, never at
import: only one process may hold the TPU library, and every xdist
worker imports this file.

Pallas families outside ``repro.kernels.TPU_READY`` are pinned as strict
expected failures with the compiler's message; making one compile turns
its test into a failure until ``TPU_READY`` names it.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.kernels import TPU_READY
from repro.kernels.common import round_up

N_DB = 262_144  # rows on one chip
N = 1000  # the paper's series length
W = N // 10
Q = 8
K = 5
BLOCK = 32  # SearchConfig's default
HBM_BYTES = 16 * 2**30  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a program compiled for an absent chip is written to the persistent
    # cache but cannot be read back without the chip: keep the cache off
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _fits_one_chip(compiled) -> None:
    ma = compiled.memory_analysis()
    used = ma.argument_size_in_bytes + ma.output_size_in_bytes
    used += ma.temp_size_in_bytes
    assert used < HBM_BYTES, used


# ------------------------------------------------------------ served path


def test_scan_sweep_compiles(one_chip):
    """The scan driver's jitted block sweep over the whole database."""
    from repro.core.cascade import _scan_search

    compiled = _scan_search.lower(
        _sds((Q, N), one_chip),
        _sds((N_DB, N), one_chip),
        _sds((), one_chip, jnp.int32),
        w=W, p=1, k=K, block=BLOCK, method="lb_improved", d=1,
    ).compile()
    _fits_one_chip(compiled)


@pytest.mark.parametrize("stage", ["lb_keogh", "lb_improved"])
def test_host_driver_stage_compiles(one_chip, stage):
    """The host driver's per-block LB stage (the planner's driver at
    this database size)."""
    from repro.core.cascade import _dense_stage_qblock

    row = _sds((Q, N), one_chip)
    _dense_stage_qblock.lower(
        stage, row, row, row, _sds((BLOCK, N), one_chip), w=W, p=1, d=1
    ).compile()


def test_host_driver_dp_chunk_compiles(one_chip):
    """The host driver's pooled 16-pair banded DP dispatch."""
    from repro.core.cascade import _dtw_pairs_block

    pairs = _sds((16, N), one_chip)
    _dtw_pairs_block.lower(pairs, pairs, w=W, p=1, d=1).compile()


@pytest.fixture(scope="module")
def four_chips(topo):
    return Mesh(
        np.array(topo.devices).reshape(4, 1), ("data", "model"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2,
    )


def _lower_sharded(mesh, rows, n=N, dtype=jnp.float32):
    from repro.core.distributed import _sharded_search_fn

    axes = ("data", "model")
    fn = _sharded_search_fn(
        mesh, axes, n // 10, 1, K, BLOCK, 4, "lb_improved", 1
    )
    return fn.lower(
        _sds((Q, n), NamedSharding(mesh, P()), dtype),
        _sds((rows, n), NamedSharding(mesh, P(axes)), dtype),
    )


def test_sharded_search_compiles(four_chips):
    """The shard_map sweep on a 4-chip mesh, database sharded by rows."""
    compiled = _lower_sharded(four_chips, 4 * N_DB).compile()
    _fits_one_chip(compiled)  # per-device figures
    hlo = compiled.as_text()
    assert "all-gather" in hlo and "all-reduce" in hlo


def test_sharded_search_compiles_float64(four_chips):
    """A float64 session's sharded sweep: the TPU lowers no float64 min
    all-reduce, so the bound exchange must not need one.  (A short
    series: emulated float64 makes the n=1000 compile take minutes.)"""
    with jax.enable_x64(True):
        _lower_sharded(four_chips, 4096, n=64, dtype=jnp.float64).compile()


# ---------------------------------------------------------- Pallas kernels

B = 256  # kernel candidates per launch
TOTAL = round_up(N + 2 * W, 2 * W + 1)  # vHGW sentinel-padded width


def _lower_envelope(s):
    from repro.kernels.envelope.kernel import envelope_pallas_padded

    return envelope_pallas_padded.lower(
        s(B, TOTAL), s(B, TOTAL), w=W, n=N, tile_b=8, interpret=False
    )


def _lower_lb_kim(s):
    from repro.kernels.lb_kim.kernel import lb_kim_qbatch_pallas

    return lb_kim_qbatch_pallas.lower(
        s(B, N), s(Q, N), s(Q, B), p=1, tile_b=8, interpret=False
    )


def _lower_lb_keogh(s):
    from repro.kernels.lb_keogh.kernel import lb_keogh_qbatch_pallas

    return lb_keogh_qbatch_pallas.lower(
        s(B, N), s(Q, N), s(Q, N), p=1, tile_b=8, interpret=False
    )


def _lower_lb_improved(s):
    from repro.kernels.lb_improved.kernel import (
        lb_improved_pass2_qbatch_pallas,
    )

    return lb_improved_pass2_qbatch_pallas.lower(
        s(Q, B, TOTAL), s(Q, B, TOTAL), s(Q, N), w=W, n=N, p=1, tile_b=8,
        interpret=False,
    )


def _lower_lb_fused(s):
    from repro.kernels.lb_fused.kernel import lb_fused_qbatch_pallas

    # the checked-in default schedule (kernels/tuning/defaults.py)
    return lb_fused_qbatch_pallas.lower(
        s(B, N), s(Q, N), s(Q, N), s(Q, N), s(Q, 1), w=W, n=N, p=1,
        tile_b=8, interpret=False, depth=2, grid="bq",
    )


def _lower_dtw(s):
    from repro.kernels.dtw.kernel import dtw_banded_pallas

    return dtw_banded_pallas.lower(
        s(1, N), s(B, N + 2 * W), s(B, 1), n=N, w=W, p=1, interpret=False,
        depth=2,
    )


LOWER = {
    "envelope": _lower_envelope,
    "lb_kim": _lower_lb_kim,
    "lb_keogh": _lower_lb_keogh,
    "lb_improved": _lower_lb_improved,
    "lb_fused": _lower_lb_fused,
    "dtw": _lower_dtw,
}

#: what the TPU compiler says about each family not yet in TPU_READY
REFUSED = {
    "envelope": "Unimplemented primitive in Pallas TPU lowering: rev "
    "(the vHGW suffix scans reverse each window block)",
    "lb_improved": "Unimplemented primitive in Pallas TPU lowering: rev "
    "(pass 2 builds the projection's envelope with vHGW suffix scans)",
    "lb_fused": "Unimplemented primitive in Pallas TPU lowering: rev "
    "(its in-VMEM pass 2 is the lb_improved vHGW sweep)",
    "dtw": "Mosaic: cannot statically prove that index in dimension 1 is a "
    "multiple of 128 (the band row is a dynamic, unaligned lane slice)",
}


def _family_param(name):
    if name in TPU_READY:
        return name
    return pytest.param(
        name, marks=pytest.mark.xfail(strict=True, reason=REFUSED[name])
    )


def test_every_family_has_a_case():
    assert set(TPU_READY) <= set(LOWER)
    assert set(LOWER) - set(TPU_READY) == set(REFUSED)


@pytest.mark.parametrize("family", [_family_param(f) for f in sorted(LOWER)])
def test_pallas_family_compiles(one_chip, family):
    compiled = LOWER[family](lambda *shape: _sds(shape, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
