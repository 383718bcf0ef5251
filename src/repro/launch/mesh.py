"""Production meshes. Functions, not module constants — importing this file
never touches jax device state."""
from __future__ import annotations

import jax

__all__ = ["make_production_mesh", "make_mesh_shape"]


def _make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 256 chips as (16 data, 16 model). Multi-pod: 2×256 with a
    leading 'pod' axis (DP across pods; PP over 'pod' in the pp demo)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_mesh_shape(shape: tuple[int, ...], axes: tuple[str, ...]):
    """Arbitrary mesh (elastic re-scale paths)."""
    return _make_mesh(shape, axes)
