"""Production meshes.

Single pod: TPU v5e-256 as (data=16, model=16).
Multi-pod : 2 pods = 512 chips as (pod=2, data=16, model=16); the "pod" axis
models the slow cross-DCN links where NoLoCo's gossip replaces all-reduce.

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state; the dry-run sets
``--xla_force_host_platform_device_count=512`` before first jax init.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_mesh", "make_production_mesh", "make_test_mesh"]


def make_mesh(shape, axes):
    """A mesh over the visible devices with Auto axis types."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(data: int = 4, model: int = 2, pod: int | None = None):
    """Small host-device mesh for CPU tests (device count forced upstream)."""
    if pod:
        return make_mesh((pod, data, model), ("pod", "data", "model"))
    return make_mesh((data, model), ("data", "model"))
