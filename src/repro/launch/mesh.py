"""Production mesh construction.

Defined as FUNCTIONS (not module constants) so importing this module never
touches jax device state — the dry-run sets the virtual device count before
first jax init, and smoke tests must keep seeing 1 device.
"""

from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 chips per pod; 2 pods in multi-pod mode (TPU v5e target)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def data_axes(mesh) -> tuple:
    """The data-parallel axes of a production mesh (includes 'pod')."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def make_device_mesh():
    """The production ``(data, model)`` axes over the devices present (all
    of them on ``data``): what a non-smoke run serves on when the host has
    fewer chips than a pod."""
    n = len(jax.devices())
    return jax.make_mesh(
        (n, 1), ("data", "model"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2,
    )


def make_host_mesh(n_devices: int = 0, axes=("data",)):
    """Small local mesh for tests/examples on whatever devices exist."""
    n = n_devices or len(jax.devices())
    return jax.make_mesh((n,), axes, axis_types=(jax.sharding.AxisType.Auto,))
