"""Production mesh construction (a FUNCTION, so importing this module never
touches jax device state)."""

from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_mesh"]


def _auto_mesh(shape, axes):
    # GSPMD-style (Auto) axes: the step functions place work with
    # with_sharding_constraint and let the partitioner resolve gathers;
    # jax.make_mesh otherwise defaults to Explicit axes.
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; multi_pod adds a leading pod=2 axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary mesh (reduced meshes for tests, elastic rescale)."""
    return _auto_mesh(shape, axes)
