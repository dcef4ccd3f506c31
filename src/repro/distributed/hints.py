"""Best-effort sharding hints usable from model code.

``hint(x, *axes)`` applies ``with_sharding_constraint`` with the requested
logical axes when (a) tracing under a mesh context, (b) every named axis
exists on that mesh, and (c) the dim divides evenly — otherwise it is a
no-op.  This lets substrate code (scan carries, MoE buffers) pin the layouts
GSPMD propagation gets wrong without coupling model code to any mesh.

Axis tokens: "dp" (all data-parallel axes: pod+data), "data", "model", None.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import numpy as np
from jax.sharding import PartitionSpec as P

__all__ = ["hint"]


def _mesh_axes():
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or not mesh.axis_names:
        return None
    return mesh


def hint(x: jax.Array, *axes) -> jax.Array:
    """Constrain array dims to mesh axes; silently no-op when impossible."""
    mesh = _mesh_axes()
    if mesh is None:
        return x
    names = mesh.axis_names
    shape = dict(zip(names, mesh.shape.values(), strict=True)) if hasattr(mesh, "shape") else {}

    spec = []
    # fewer axes than dims is allowed: the tail is padded with None below
    for dim, ax in zip(x.shape, axes, strict=False):
        if ax == "dp":
            cand = tuple(a for a in ("pod", "data") if a in names)
            ax = cand if len(cand) > 1 else (cand[0] if cand else None)
        if ax is None:
            spec.append(None)
            continue
        ax_t = ax if isinstance(ax, tuple) else (ax,)
        if not all(a in names for a in ax_t):
            spec.append(None)
            continue
        size = int(np.prod([shape.get(a, 1) for a in ax_t]))
        spec.append(ax if dim % max(size, 1) == 0 else None)
    spec += [None] * (x.ndim - len(spec))
    try:
        return jax.lax.with_sharding_constraint(x, P(*spec))
    except Exception:
        return x
