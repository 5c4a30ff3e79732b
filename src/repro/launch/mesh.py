"""Production mesh construction.

Defined as functions (never module-level constants) so importing this
module never touches jax device state — jax locks the device count on
first backend init, and only launch/dryrun.py sets the 512-device flag.
"""

from __future__ import annotations

import jax


def _auto(n_axes: int) -> tuple:
    """Auto axes: shardings are constraints the compiler propagates.
    ``jax.make_mesh`` defaults to Explicit axes, which refuse the
    ``with_sharding_constraint`` specs the dry-run and search use."""
    return (jax.sharding.AxisType.Auto,) * n_axes


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """v5e pod mesh: 16x16 = 256 chips; multi-pod adds a 2-pod DCN axis.

    REPRO_SMALL_MESH=1 shrinks to (2,2)/(2,2,2) so the dry-run *machinery*
    can be exercised in tests with 8 host devices; production cells always
    use the full 256/512-chip meshes.
    """
    import os

    if os.environ.get("REPRO_SMALL_MESH") == "1":
        shape = (2, 2, 2) if multi_pod else (2, 2)
    else:
        shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_host_mesh(model_axis: int = 1) -> jax.sharding.Mesh:
    """Small mesh over whatever devices exist (tests / CPU examples)."""
    n = jax.device_count()
    data = n // model_axis
    return jax.make_mesh(
        (data, model_axis), ("data", "model"), axis_types=_auto(2)
    )


def mesh_axis_sizes(mesh: jax.sharding.Mesh) -> dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))
