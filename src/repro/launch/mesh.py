"""Production meshes. Functions, not module constants: importing this module
never touches jax device state (the dry-run sets XLA_FLAGS first)."""

from __future__ import annotations

import jax


def _auto_mesh(shape: tuple, axes: tuple):
    """``jax.make_mesh`` with every axis ``Auto``: the mesh GSPMD
    partitions, and the only one ``with_sharding_constraint`` accepts
    (``make_mesh`` defaults to ``Explicit`` axes)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """The assignment's production mesh: 16×16 = 256 chips per pod
    ("data", "model"), or 2 pods = 512 chips ("pod", "data", "model").

    When more host devices exist than the mesh needs (the dry-run process
    exposes 512 for both variants), the first prod(shape) devices are used.
    """
    import math

    import numpy as np

    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = math.prod(shape)
    devices = jax.devices()
    if len(devices) == n:
        return _auto_mesh(shape, axes)
    if len(devices) > n:
        return jax.sharding.Mesh(
            np.asarray(devices[:n]).reshape(shape), axes)
    raise RuntimeError(
        f"need {n} devices for mesh {dict(zip(axes, shape))}, have "
        f"{len(devices)} — run under XLA_FLAGS="
        f"--xla_force_host_platform_device_count={n}")


def make_test_mesh(*, multi_pod: bool = False):
    """Small host-device mesh with the same axis names (8 devices),
    for integration tests run under xla_force_host_platform_device_count=8."""
    shape = (2, 2, 2) if multi_pod else (4, 2)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_local_mesh():
    """Single-device mesh (smoke tests / examples on this CPU container)."""
    return _auto_mesh((1, 1), ("data", "model"))
