"""Mesh construction: general shapes plus the production presets.

`make_mesh` builds a mesh of ANY (shape, axes) that fits the available
device count — the SPMD harness uses `make_mesh((4, 2), ("workers",
"data"))` on 8 forced host devices exactly like the dry-run uses the
512-chip presets below.  The presets:

Single pod : (data=16, model=16)          = 256 chips (TPU v5e-256 class)
Multi-pod  : (pod=2, data=16, model=16)   = 512 chips, pod axis over DCN

FUNCTIONS, not module-level constants: importing this module never touches
jax device state (the dry-run sets XLA_FLAGS before any jax import).
"""
from __future__ import annotations

import math
from typing import Sequence

import jax
from jax.experimental import mesh_utils
from jax.sharding import AxisType, Mesh


def make_mesh(shape: Sequence[int], axes: Sequence[str]) -> Mesh:
    """A mesh of the requested shape over the first prod(shape) devices.

    Errors (rather than silently reshaping) when the device count is too
    small — on CPU the count is set with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` BEFORE the first
    jax import.
    """
    shape = tuple(int(s) for s in shape)
    axes = tuple(axes)
    if len(shape) != len(axes):
        raise ValueError(f"mesh shape {shape} and axes {axes} disagree on "
                         "rank")
    if any(s < 1 for s in shape):
        raise ValueError(f"mesh shape {shape} must be positive")
    n = math.prod(shape)
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {dict(zip(axes, shape))}, found "
            f"{len(devices)} — set XLA_FLAGS=--xla_force_host_platform_"
            f"device_count={n} before importing jax (CPU), or run on a "
            "large enough slice")
    dev_mesh = mesh_utils.create_device_mesh(shape, devices[:n])
    return Mesh(dev_mesh, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    if multi_pod:
        return make_mesh((2, 16, 16), ("pod", "data", "model"))
    return make_mesh((16, 16), ("data", "model"))


def mesh_axis_sizes(mesh) -> dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))
