"""Production mesh construction (multi-pod dry-run contract).

A FUNCTION, not a module-level constant, so importing this module never
touches jax device state.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    # Auto axes: the model code places arrays with with_sharding_constraint,
    # which rejects the Explicit axes jax.make_mesh defaults to
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod.

    The "pod" axis is pure data parallelism across pods — each pod maps onto
    one of the paper's DP serving engines, so the multi-pod mesh is a faithful
    scale-up of the paper's two-engine testbed (DESIGN.md §5).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary mesh for tests / small-scale functional runs."""
    return _mesh(shape, axes)


def batch_axes_of(mesh) -> tuple:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))
