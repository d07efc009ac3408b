"""Mesh construction.

A function (never a module-level constant), so importing this module
never touches jax device state.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh


def make_smoke_mesh(shape=(1, 1), axes=("data", "model")) -> Mesh:
    return Mesh(np.array(jax.devices()[: int(np.prod(shape))]).reshape(shape), axes)
