"""Production meshes.

IMPORTANT: functions, not module-level constants — importing this module never
touches jax device state.  The dry-run sets XLA_FLAGS for 512 placeholder
devices *before* importing jax (see dryrun.py); everything else sees the real
device count.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """TPU v5e production mesh: 16x16 per pod; 2 pods multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(data: Optional[int] = None, model: int = 1) -> Mesh:
    """Mesh over whatever devices exist (CPU smoke tests, examples)."""
    n = len(jax.devices())
    if data is None:
        data = n // model
    if model > 1:
        return jax.make_mesh((data, model), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
    return jax.make_mesh((data,), ("data",), axis_types=(AxisType.Auto,))


def data_mesh(devices: Sequence) -> Mesh:
    """1-D data-parallel mesh over an explicit device subset (elastic
    resize: the first k devices)."""
    return Mesh(np.asarray(devices), ("data",), axis_types=(AxisType.Auto,))


def mesh_chips(mesh: Mesh) -> int:
    n = 1
    for v in mesh.shape.values():
        n *= v
    return n
