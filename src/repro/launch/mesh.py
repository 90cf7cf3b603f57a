"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so
importing this module never touches jax device state.  The dry-run script
sets ``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any
jax import to obtain the placeholder devices.  Every mesh axis is
``AxisType.Auto``.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """Arbitrary mesh (elastic scaling uses smaller DP extents)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_abstract_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """Device-free ``AbstractMesh`` with the same axis types."""
    return jax.sharding.AbstractMesh(shape, axes,
                                     axis_types=(AxisType.Auto,) * len(axes))
