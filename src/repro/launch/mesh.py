"""Production meshes.  A function (not a module-level constant) so importing
never touches jax device state — the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 *before* any jax init."""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    # Auto axes: the model stack places arrays with with_sharding_constraint,
    # which Explicit axes (jax.make_mesh's default) reject
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_test_mesh(n_data: int = 2, n_model: int = 2, *, multi_pod=False):
    """Small mesh over however many (host-platform) devices tests configured."""
    if multi_pod:
        return _mesh((2, n_data, n_model), ("pod", "data", "model"))
    return _mesh((n_data, n_model), ("data", "model"))


def dp_axes(mesh) -> tuple:
    """Axes that carry data parallelism (pod included when present)."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def tp_size(mesh) -> int:
    return mesh.shape["model"]


def dp_size(mesh) -> int:
    n = 1
    for a in dp_axes(mesh):
        n *= mesh.shape[a]
    return n
