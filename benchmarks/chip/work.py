"""Work counts of one projection step, and the peaks of the chips measured.

The counts follow from the algorithm's shapes, not from any implementation:

* FLOPs: 90 for the advection-diffusion of the three face velocities, 7 for
  the divergence, 10 for each Jacobi sweep and 9 for the projection, per
  cell (the analytic count of ``benchmarks/bench_stencil._flops_per_step``).
* Compulsory bytes: the step's state read once and its output written once:
  seven float32 fields in (vx, vy, vz, p and the three wall masks) and four
  out (vx, vy, vz, p), per cell.  Jacobi sweeps fused or blocked in fast
  memory cannot go below this, so a share of the roofline from it stays
  under 100% -- unless a kernel keeps a member's state in fast memory
  across steps, which this count does not foresee.
"""
from __future__ import annotations

FLOPS_PER_CELL_FIXED = 90 + 7 + 9
FLOPS_PER_CELL_PER_SWEEP = 10
FIELDS_IN, FIELDS_OUT, BYTES_PER_VALUE = 7, 4, 4

# Google Cloud documentation, "TPU v5e": 197 TFLOP/s (bf16), 819 GB/s and
# 16 GB of HBM per chip.  Keyed by ``jax.Device.device_kind``.
PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind``; a kind not in the table is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None


def step_flops(cells: int, jacobi_iters: int) -> int:
    return cells * (FLOPS_PER_CELL_FIXED
                    + FLOPS_PER_CELL_PER_SWEEP * jacobi_iters)


def step_bytes(cells: int) -> int:
    return cells * (FIELDS_IN + FIELDS_OUT) * BYTES_PER_VALUE


def least_step_seconds(cells: int, jacobi_iters: int,
                       device_kind: str) -> tuple[float, str]:
    """The least time one chip could take for a step over ``cells`` cells,
    and which term bounds it (``"bytes"`` or ``"flops"``)."""
    pk = peaks(device_kind)
    t_bytes = step_bytes(cells) / pk["bytes_per_s"]
    t_flops = step_flops(cells, jacobi_iters) / pk["flops_per_s"]
    return (t_bytes, "bytes") if t_bytes >= t_flops else (t_flops, "flops")
