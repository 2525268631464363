"""Production mesh construction.

Defined as FUNCTIONS (not module constants) so importing this module never
touches jax device state — required because the dry-run overrides the
device count via XLA_FLAGS before first jax init, while smoke tests run
on the single real CPU device.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips with a leading 'pod'
    axis (the multi-pod dry-run proves the pod axis shards)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary mesh (training, elastic-restore targets, tests).

    Every axis is ``Auto``: the training step leaves propagation to XLA
    and annotates shardings with ``with_sharding_constraint``, which
    ``jax.make_mesh``'s default Explicit axes would reject."""
    axes = tuple(axes)
    return jax.make_mesh(tuple(shape), axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def dp_axes(mesh) -> tuple:
    """The data-parallel axes of a mesh (pod axis included when present)."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def tp_axis(mesh) -> str:
    return "model"
