"""Logical-axis -> mesh-axis resolution with divisibility fallbacks.

The spike mesh (:mod:`repro.distributed.spike_mesh`) names the axes of
its arrays logically — ``neuron``, ``batch``, ``source``, ``time`` — and
:data:`~repro.distributed.spike_mesh.SNN_RULES` maps ``neuron`` and
``batch`` onto the mesh axes of the same names. :func:`spec_for` turns a
logical-axis tuple and an array shape into the ``PartitionSpec`` each
dispatch uses: the weight image's columns, the carries and the rasters
shard over ``neuron``; the slots over ``batch``; the source and time axes
never shard, since every device reads the full all-gathered source vector.

Every rule is checked against the actual dim: a dim the mesh axis does
not divide, a size-1 axis, or a mesh axis already taken by an earlier dim
of the same array replicates instead.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import numpy as np
from jax.sharding import PartitionSpec

__all__ = ["PartitionRules", "spec_for"]


@dataclasses.dataclass(frozen=True)
class PartitionRules:
    """Logical axis name -> mesh axis (or tuple of mesh axes)."""

    rules: dict[str, Any] = dataclasses.field(default_factory=dict)


def _axis_size(mesh, mesh_axes) -> int:
    if mesh_axes is None:
        return 1
    if isinstance(mesh_axes, str):
        mesh_axes = (mesh_axes,)
    return int(np.prod([mesh.shape[a] for a in mesh_axes]))


def spec_for(logical_axes, shape, mesh, rules: PartitionRules
             ) -> PartitionSpec:
    """Resolve logical axes to a PartitionSpec with divisibility checks."""
    out = []
    used: set[str] = set()
    for ax, dim in zip(logical_axes, shape):
        mesh_axes = rules.rules.get(ax) if ax is not None else None
        if mesh_axes is None:
            out.append(None)
            continue
        names = (mesh_axes,) if isinstance(mesh_axes, str) else tuple(
            mesh_axes)
        if any(n in used for n in names):
            out.append(None)
            continue
        size = _axis_size(mesh, names)
        if size <= 1 or dim % size != 0:
            out.append(None)
            continue
        used.update(names)
        out.append(mesh_axes if isinstance(mesh_axes, str) else names)
    while out and out[-1] is None:
        out.pop()
    return PartitionSpec(*out)
