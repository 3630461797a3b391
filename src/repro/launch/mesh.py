"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module never touches
jax device state (the dry-run sets XLA_FLAGS before any jax init; tests see
the real 1-CPU world).

Every mesh in the repo is built by :func:`make_mesh`, whose axes are
``AxisType.Auto``: ``jax.make_mesh`` alone gives ``Explicit`` axes, which
``autoshard.hint``'s ``with_sharding_constraint`` refuses.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...], devices=None):
    """Mesh of ``shape`` over ``axes`` with Auto axis types (elastic restore
    targets, launchers, tests).  ``devices`` defaults to all of them."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips/pod ('data', 'model'); 2x16x16 = 512 with a leading
    'pod' axis.  DP runs over pod x data; TP/EP over model."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_serving_mesh(shape: tuple[int, int] | None = None):
    """('data', 'model') mesh for the sharded serving path.

    Default puts every visible device on the model axis (pure
    tensor-parallel KV-head sharding); pass ``shape=(data, model)`` to
    split off a data/slot-parallel axis."""
    return make_mesh(shape or (1, jax.device_count()), ("data", "model"))


def data_axes(mesh) -> tuple[str, ...]:
    """Axes gradients are reduced over (everything that is not 'model')."""
    return tuple(a for a in mesh.axis_names if a != "model")


def mesh_tp(mesh) -> int:
    return dict(zip(mesh.axis_names, mesh.devices.shape)).get("model", 1)
