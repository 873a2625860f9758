"""Production meshes.

``make_production_mesh`` is a FUNCTION (never module-level state) so that
importing this module touches no jax device machinery.  The dry-run forces
512 host devices via XLA_FLAGS before any jax import; smoke tests and
benchmarks see the real single CPU device.

Mesh geometry (TPU v5e, per the brief):
  single-pod:  (data=16, model=16)            = 256 chips
  multi-pod:   (pod=2, data=16, model=16)     = 512 chips
The ``model`` axis carries TP/EP/SP; ``data`` (x ``pod``) carries DP.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh

__all__ = ["make_production_mesh", "make_test_mesh"]


def _make_mesh(shape, axes) -> Mesh:
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_test_mesh(shape=(2, 4), axes=("data", "model")) -> Mesh:
    """Small mesh for the 8-device subprocess tests."""
    return _make_mesh(shape, axes)
