"""Production mesh construction (TPU v5e pods; host-device placeholders in
the dry-run).  Defined as functions so importing never touches jax device
state."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def node_axes(mesh) -> tuple:
    """Mesh axes hosting the BRIDGE node dimension."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def num_nodes(mesh) -> int:
    import math

    return math.prod(mesh.shape[a] for a in node_axes(mesh))


def make_host_mesh(data: int = 2, model: int = 2):
    """Tiny mesh over host CPU devices for tests (requires
    XLA_FLAGS=--xla_force_host_platform_device_count >= data*model)."""
    return jax.make_mesh((data, model), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
