"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — required because the dry-run must set
``xla_force_host_platform_device_count`` before jax initializes.
"""
from __future__ import annotations

import jax


def _make_mesh(shape, axes):
    return jax.make_mesh(
        tuple(shape), tuple(axes),
        axis_types=(jax.sharding.AxisType.Auto,) * len(shape))


def make_production_mesh(*, multi_pod: bool = False, shape=None, axes=None):
    """Default production meshes:
        single-pod: (16, 16)   axes ("data", "model")   = 256 chips
        multi-pod:  (2, 16, 16) axes ("pod", "data", "model") = 512 chips

    The "pod" axis is just an outer FSDP/DP axis; scaling to N pods
    (N*256 chips) is ``shape=(N, 16, 16)`` — no code change."""
    if shape is None:
        shape = (2, 16, 16) if multi_pod else (16, 16)
        axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    assert axes is not None and len(axes) == len(shape)
    return _make_mesh(shape, axes)


def make_host_mesh():
    """Trivial 1-device mesh for CPU training/tests."""
    return _make_mesh((1,), ("data",))


def slice_mesh(devices, axis: str = "data"):
    """1-D mesh over an explicit device list — the per-fleet-slice mesh the
    disaggregated trainer (DESIGN.md §12) publishes onto.  Unlike
    ``make_production_mesh`` this takes the devices verbatim (a slice from
    ``repro.dist.placement.carve``), so it composes with any carving."""
    import numpy as np

    return jax.sharding.Mesh(np.asarray(devices), (axis,))


def set_ambient_mesh(mesh):
    """Make ``mesh`` the process's ambient mesh (``jax.set_mesh``)."""
    jax.set_mesh(mesh)
