"""Production mesh definitions (TPU v5e target).

Defined as FUNCTIONS so importing this module never touches jax device
state.  The dry-run sets XLA_FLAGS=--xla_force_host_platform_device_count=512
*before* any jax import; everything else sees the real (single) device.
"""
from __future__ import annotations

import jax

SINGLE_POD = (16, 16)          # 256 chips
MULTI_POD = (2, 16, 16)        # 2 pods x 256 chips


def make_production_mesh(*, multi_pod: bool = False):
    shape = MULTI_POD if multi_pod else SINGLE_POD
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, have {len(devices)}; the "
            "dry-run must set XLA_FLAGS=--xla_force_host_platform_device_"
            "count=512 before importing jax")
    import numpy as np
    return jax.sharding.Mesh(
        np.asarray(devices[:n]).reshape(shape), axes)


def make_host_mesh(model: int = 1, data: int = 1):
    """Small mesh over however many local devices exist (tests)."""
    n = len(jax.devices())
    model = min(model, n)
    data = max(1, min(data, n // model))
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


def batch_axes(mesh) -> tuple:
    """Mesh axes the global batch shards over."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)
