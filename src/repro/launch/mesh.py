"""Production mesh construction.

Functions, not module-level constants, so importing this module never touches
jax device state (device count is locked at first jax init).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _make_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axes: the model zoo places its arrays
    with ``in_shardings`` / ``with_sharding_constraint`` and lets the
    compiler propagate the rest (JAX's default mesh axes are Explicit)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips) mesh.

    The ``pod`` axis is the federated-client axis of the HFL system: each pod
    is one hospital/client; parameters replicate across it and only the HFL
    head-pool blend communicates over it.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Tiny mesh over the real local devices (tests / smoke runs)."""
    n = len(jax.devices())
    data = min(data, n)
    return _make_mesh((data, max(1, min(model, n // data))), ("data", "model"))


def mesh_axis_sizes(mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))
