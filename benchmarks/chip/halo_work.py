"""Halo bytes of one decomposed projection step, per device.

Counted from the algorithm's shapes, as ``work.py`` counts the step: the
configuration's grid, mesh and Jacobi sweeps, nothing of the program.  A
step fills ghost cells as ``reference.py`` does: each of the three
velocities one cell deep on both sides of every axis, the three advected
velocities one cell deep on the low side (the divergence), p on both sides
once a sweep, and p on the high side (the projection).  Axes are filled in
the order x, y, z, each padding the already padded earlier ones, so a
strip across a later axis carries the earlier axes' ghosts.  Across a
split axis each ghost strip comes from the neighbouring chip: those are
the bytes a device has to receive.
"""
from __future__ import annotations

import math

BYTES_PER_VALUE = 4


def local_shape(config: dict) -> tuple[list, set]:
    """One device's block of the grid, and the axes split over the mesh."""
    shape = list(config["grid"])
    mesh = config.get("mesh") or {}
    extents = dict(zip(mesh.get("axes", ()), mesh.get("shape", ())))
    split = set()
    for axis, name in mesh.get("decomposition", ()):
        shape[axis] //= extents[name]
        split.add(axis)
    return shape, split


def pad_bytes(shape, split: set, sides) -> int:
    """Bytes one device receives to pad a block of ``shape`` by
    ``sides[axis] = (lo, hi)`` ghost rows, axis by axis."""
    shape = list(shape)
    total = 0
    for axis, (lo, hi) in enumerate(sides):
        if axis in split:
            plane = math.prod(shape) // shape[axis]
            total += (lo + hi) * plane * BYTES_PER_VALUE
        shape[axis] += lo + hi
    return total


def step_bytes(config: dict) -> int:
    """Halo bytes a device receives in one step (0 without a mesh)."""
    shape, split = local_shape(config)
    dims = len(shape)
    both, low, high = ((1, 1),) * dims, ((1, 0),) * dims, ((0, 1),) * dims
    return (3 * pad_bytes(shape, split, both)
            + 3 * pad_bytes(shape, split, low)
            + config["jacobi_iters"] * pad_bytes(shape, split, both)
            + pad_bytes(shape, split, high))
