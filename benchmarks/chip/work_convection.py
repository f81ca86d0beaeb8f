"""Work counts of one Rayleigh-Benard step (``reference_convection.py``),
per cell, from the algorithm's shapes and not from any implementation.

* FLOPs, each operation counted once per cell, a face's value shared by
  the two cells on either side:
  - 90: advection-diffusion of the three face velocities (``work.py``);
  - 4: buoyancy, the mean of two cells' T (an add and a halving), times
    dt, added to vx;
  - 27: the temperature's advection-diffusion: three face fluxes, each
    the mean of two cells' T (2) times the face velocity (1), so 9; their
    divergence, three differences, two adds and a scaling by 1/h, so 6;
    the 7-point Laplacian, five adds of the six neighbours, 6 T and its
    subtraction, and a scaling by 1/h^2, so 8; times kappa, minus the
    advection, times dt, plus T, so 4;
  - 7: the divergence, 10 each Jacobi sweep and 9 the projection
    (``work.py``).
* Compulsory bytes: the step's state read once and its output written
  once: eight float32 fields in (vx, vy, vz, p, T and the three wall
  masks) and five out (vx, vy, vz, p, T), 52 bytes a cell.
"""
from __future__ import annotations

import work

FLOPS_PER_CELL_FIXED = 90 + 4 + 27 + 7 + 9
FLOPS_PER_CELL_PER_SWEEP = 10
FIELDS_IN, FIELDS_OUT = 8, 5


def step_flops(cells: int, jacobi_iters: int) -> int:
    return cells * (FLOPS_PER_CELL_FIXED
                    + FLOPS_PER_CELL_PER_SWEEP * jacobi_iters)


def step_bytes(cells: int) -> int:
    return cells * (FIELDS_IN + FIELDS_OUT) * work.BYTES_PER_VALUE


def least_step_seconds(cells: int, jacobi_iters: int,
                       device_kind: str) -> tuple[float, str]:
    """The least time one chip could take for a step over ``cells``
    cells, and which term bounds it (``"bytes"`` or ``"flops"``)."""
    pk = work.peaks(device_kind)
    t_bytes = step_bytes(cells) / pk["bytes_per_s"]
    t_flops = step_flops(cells, jacobi_iters) / pk["flops_per_s"]
    return (t_bytes, "bytes") if t_bytes >= t_flops else (t_flops, "flops")
