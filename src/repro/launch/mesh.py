"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so
importing this module never touches jax device state; only the dry-run
sets ``xla_force_host_platform_device_count``.

Meshes are built with ``Auto`` axes: placement comes from the
``repro.dist`` recipes through ``constrain``, not from sharding-in-types.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """Arbitrary mesh (elastic re-plans, tests, PP stage meshes)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 (data, model) single pod or 2x16x16 (pod, data, model)."""
    if multi_pod:
        return make_mesh((2, 16, 16), ("pod", "data", "model"))
    return make_mesh((16, 16), ("data", "model"))


def use_mesh(mesh):
    """Context manager installing ``mesh`` as the ambient mesh — the one
    ``repro.dist.sharding.constrain`` resolves logical axes against."""
    return jax.set_mesh(mesh)
