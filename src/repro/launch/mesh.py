"""Production mesh construction.

Defined as functions (never module-level constants) so importing this module
never touches jax device state — required for the smoke tests to see a
single CPU device while the dry-run sees 512 fake hosts.
"""
from __future__ import annotations

import jax


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_test_mesh(shape=(1, 1), axes=("data", "model")):
    """Tiny mesh over however many (possibly fake) devices exist."""
    return _make_mesh(shape, axes)


def mesh_axes(mesh) -> dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def dp_axes(mesh) -> tuple[str, ...]:
    """Axes that carry the batch (pod + data when multi-pod)."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)
