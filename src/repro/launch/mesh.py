"""Production mesh construction (assignment contract).

Defined as FUNCTIONS so importing this module never touches jax device
state — the dry-run sets XLA_FLAGS before the first jax call and only then
asks for the mesh.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_test_mesh(shape=(2, 4), axes=("data", "model")):
    """Small mesh for CPU multi-device tests (8 fake host devices)."""
    return _make_mesh(shape, axes)


def make_d_mesh(ndev: int | None = None, axis: str = "d"):
    """Flat one-axis mesh over ``ndev`` (default: all) devices.

    The layout the D-sharded incremental state machine wants
    (``core/dist_state.py``): every (N, D) data strip splits its LAST axis
    over this single axis, all (N, N) strips are replicated, and ring
    (ppermute) pipelining has one well-defined ring to run on.  Multi-axis
    meshes also work everywhere psum-based (the D axis is sharded over all
    axes jointly); only the ring-overlap path requires this flat form.
    """
    n = len(jax.devices()) if ndev is None else int(ndev)
    return _make_mesh((n,), (axis,))
