"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this
module never touches jax device state — required because the dry-run must
set XLA_FLAGS before the first jax device query.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    need = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < need:
        raise RuntimeError(
            f"mesh {shape} needs {need} devices but only {len(devices)} present; "
            "the dry-run launcher must set XLA_FLAGS=--xla_force_host_platform_"
            "device_count=512 before any jax import"
        )
    if len(devices) == need:
        # topology-aware device ordering on real TPU slices
        return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))
    # device superset (e.g. single-pod mesh inside the 512-device dry-run
    # process): take the first pod's worth.
    return Mesh(np.array(devices[:need]).reshape(shape), axes)


def make_smoke_mesh(shape=(1, 1), axes=("data", "model")) -> Mesh:
    return Mesh(np.array(jax.devices()[: int(np.prod(shape))]).reshape(shape), axes)
