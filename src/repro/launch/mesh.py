"""Production mesh definition (a FUNCTION — importing this module never
touches jax device state)."""

from __future__ import annotations


def make_production_mesh(*, multi_pod: bool = False):
    from repro.distributed.mesh import make_mesh

    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)
