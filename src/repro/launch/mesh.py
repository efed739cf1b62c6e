"""Production mesh construction.

A FUNCTION, not a module constant: importing this module must never touch
jax device state (the dry-run sets XLA_FLAGS before first jax init; smoke
tests and benches run on the single real device).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    # Auto axes: the model places activations with sharding constraints
    # and leaves the rest to the partitioner, which Explicit axes (the
    # default of jax.make_mesh) refuse
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """(16, 16) ("data", "model") single pod; (2, 16, 16) ("pod", "data",
    "model") for the 512-chip two-pod dry-run."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """Arbitrary mesh (tests / elastic rescale)."""
    return _auto_mesh(shape, axes)


def data_axes_of(mesh) -> tuple[str, ...]:
    """Every non-'model' axis is a data axis (pod included)."""
    return tuple(a for a in mesh.axis_names if a != "model")
