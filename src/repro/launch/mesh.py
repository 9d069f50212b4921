"""Production meshes.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run must set XLA_FLAGS before any device query).
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh

from repro.sharding import make_mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """Single pod: (data=16, model=16) = 256 chips.
    Multi-pod: (pod=2, data=16, model=16) = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model_parallel: int = 1) -> Mesh:
    """Whatever devices this host actually has — smoke tests / examples."""
    n = len(jax.devices())
    mp = model_parallel if n % model_parallel == 0 else 1
    return make_mesh((n // mp, mp), ("data", "model"))


def mesh_device_count(mesh: Mesh) -> int:
    return int(np.prod(mesh.devices.shape))
