"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this module
does not touch jax device state — the dry-run must set
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before jax init,
and smoke tests must keep seeing 1 device.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import numpy as np
from jax.sharding import AxisType, Mesh

__all__ = ["make_production_mesh", "make_smoke_mesh", "POD_SHAPE"]

POD_SHAPE = (16, 16)  # one v5e pod: 256 chips


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """The assignment's production mesh: 16x16 per pod, 2 pods multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    # Auto axes: hints.py steers GSPMD propagation, and jax.make_mesh
    # would otherwise default to Explicit
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_smoke_mesh(
    data: Optional[int] = None, model: Optional[int] = None
) -> Mesh:
    """Small mesh over whatever devices exist (tests / examples)."""
    n = jax.device_count()
    if data is None or model is None:
        model = 1
        data = n
        while data % 2 == 0 and model < data:
            data //= 2
            model *= 2
    assert data * model <= n, (data, model, n)
    devs = np.asarray(jax.devices()[: data * model]).reshape(data, model)
    return Mesh(devs, ("data", "model"), axis_types=(AxisType.Auto,) * 2)
