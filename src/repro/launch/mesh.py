"""Production mesh construction (single-pod 16×16, multi-pod 2×16×16).

A FUNCTION, not a module-level constant, so importing this module never
touches jax device state.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

from ..compat import mesh_axis_sizes as _mesh_axis_sizes

SINGLE_POD = (16, 16)
MULTI_POD = (2, 16, 16)


def _make_mesh(shape, axes):
    """``jax.make_mesh`` with ``Auto`` axes. jax 0.9 defaults to ``Explicit``
    axes, under which the programs here (written for sharding propagation)
    refuse to trace: slices of sharded dims, gathers without
    ``out_sharding=``, and shard_map closing over sharded inputs."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_test_mesh(data: int = 1, model: int = 1):
    """Tiny mesh over however many (host) devices exist — for smoke tests."""
    return _make_mesh((data, model), ("data", "model"))


def make_graph_mesh(devices: int | None = None):
    """1-D mesh with the ``graph`` axis that owns graph partitions.

    ``devices=None`` spans every visible device — and ``jax.devices()`` is the
    GLOBAL list: in a ``jax.distributed`` process group
    (launch/multihost.py initialize_distributed) the same call on every
    process yields one mesh over all processes' devices, in process-major
    order, so graph-axis position d belongs to process
    ``jax.devices()[d].process_index``. A single-device (and single-process)
    mesh is the degenerate case the elastic runtime treats identically
    (DESIGN.md §6, §10). Partitions are assigned round-robin to axis
    positions — see launch/sharding.py partition_row / partition_device.
    """
    n = len(jax.devices()) if devices is None else int(devices)
    return _make_mesh((n,), ("graph",))


def mesh_axis_sizes(mesh) -> dict:
    return _mesh_axis_sizes(mesh)


def num_chips(mesh) -> int:
    return int(mesh.devices.size)
