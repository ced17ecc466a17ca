"""Production mesh construction.

Defined as a FUNCTION (never a module-level constant) so importing this
module never touches JAX device state.  The dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import; the single-pod mesh then uses the first 256 of the 512
placeholder devices, the multi-pod mesh all 512.  Activate a mesh with
``jax.set_mesh(mesh)``.
"""

from __future__ import annotations

import jax


def _make_mesh(shape, axes, devices):
    """``jax.make_mesh`` with explicit Auto axis types."""
    axis_types = (jax.sharding.AxisType.Auto,) * len(axes)
    return jax.make_mesh(shape, axes, axis_types, devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    devices = jax.devices()[:n]
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, have {len(devices)} — "
            "the dry-run must set XLA_FLAGS=--xla_force_host_platform_"
            "device_count=512 before importing jax"
        )
    return _make_mesh(shape, axes, devices)


def make_debug_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for CPU integration tests (requires forced devices)."""
    n = 1
    for s in shape:
        n *= s
    return _make_mesh(shape, axes, jax.devices()[:n])
