"""Mesh construction and logical axis conventions.

Physical axes (mandated by the production footprint):
    pod    — crosses the slow interconnect tier (2 pods in the multi-pod run)
    data   — intra-pod, batch / FSDP axis (16)
    model  — intra-pod, tensor/sequence/expert axis (16)

Logical use:
    batch                -> ('pod', 'data')
    sequence (attention) -> 'model'   (sequence parallelism: every sharded
                             dim must divide 16, head counts often don't)
    d_ff / flat qkv dims / experts / vocab -> 'model'
    param storage        -> 2D ('data', 'model') (ZeRO-3-style storage)
    PQ shards (serving)  -> ('pod', 'data', 'model') flattened
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import AxisType, Mesh

AXIS_POD = "pod"
AXIS_DATA = "data"
AXIS_MODEL = "model"


def make_mesh(
    shape: Tuple[int, ...],
    axes: Tuple[str, ...],
    devices=None,
) -> Mesh:
    """Auto-typed mesh (sharding-in-types pinned down explicitly)."""
    axis_types = (AxisType.Auto,) * len(axes)
    if devices is not None:
        import numpy as np

        return Mesh(np.asarray(devices).reshape(shape), axes,
                    axis_types=axis_types)
    return jax.make_mesh(shape, axes, axis_types=axis_types)


def batch_axes(mesh: Mesh) -> Tuple[str, ...]:
    """Axes the global batch is sharded over."""
    return tuple(a for a in (AXIS_POD, AXIS_DATA) if a in mesh.axis_names)


def mesh_geometry(mesh: Mesh) -> Tuple[int, int]:
    """(npods, chips_per_pod)."""
    npods = mesh.shape.get(AXIS_POD, 1)
    chips = 1
    for a, n in mesh.shape.items():
        chips *= n
    return npods, chips // npods


def local_fits(mesh: Mesh, dim: int, axis: str = AXIS_MODEL) -> bool:
    return dim % mesh.shape[axis] == 0
