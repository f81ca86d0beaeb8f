"""Roofline model: per-chip hardware constants + term computation.

Used by the tile autotuner (napkin math before lowering), the dry-run
analyzer (terms from compiled HLO), the perf accounting layer
(``repro.obs.perf``), and the benchmark harness.  Chips live in a small
registry so utilization is always reported against the peaks of the
hardware that actually ran — ``resolve_chip("auto")`` picks the entry
matching ``jax.devices()[0]`` (TPUs by ``device_kind``; a CI CPU lane
reports against host-class peaks) and raises for any device it has no
peaks for.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Chip:
    name: str = "tpu-v5e"
    peak_flops_bf16: float = 197e12   # FLOP/s
    peak_flops_fp32: float = 98.5e12  # MXU fp32 ~ half bf16
    hbm_bandwidth: float = 819e9      # B/s
    hbm_bytes: float = 16e9
    ici_link_bandwidth: float = 50e9  # B/s per link (~ per direction)
    ici_links: int = 4                # 2D torus: ±x, ±y
    vmem_bytes: float = 128 * 2**20

    def peak_flops(self, dtype: str = "bf16") -> float:
        return self.peak_flops_bf16 if dtype in ("bf16", "bfloat16") else self.peak_flops_fp32


# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM at
# 819 GB/s, 1,600 Gbit/s of inter-chip interconnect (4 links x 50 GB/s)
V5E = Chip()

# Deliberately round host-class numbers (a few vector cores of XLA:CPU,
# dual-channel DDR, "interconnect" = shared memory between forced host
# devices): utilization on the CPU lane is then labeled against an honest
# same-order peak instead of a TPU's — the absolute percentages stay
# rough, but ratios across runs (what the regression gate compares) are
# meaningful.
CPU_HOST = Chip(
    name="cpu-host",
    peak_flops_bf16=2e11,
    peak_flops_fp32=2e11,
    hbm_bandwidth=3e10,
    hbm_bytes=8e9,
    ici_link_bandwidth=1e10,
    ici_links=1,
    vmem_bytes=32 * 2**20,     # L2/L3-class working set
)

CHIPS: dict[str, Chip] = {
    "tpu-v5e": V5E,
    "cpu-host": CPU_HOST,
}

# jax device_kind -> registry name; a TPU generation absent here has no
# peaks in this repo and must not borrow another's
_TPU_KIND_CHIP = {"TPU v5 lite": "tpu-v5e"}


def chip_for_device(platform: str, device_kind: str) -> Chip:
    """The registry entry for one ``jax.Device`` (its ``platform`` and
    ``device_kind``); raises for a device the registry has no peaks for."""
    if platform == "tpu" and device_kind in _TPU_KIND_CHIP:
        return CHIPS[_TPU_KIND_CHIP[device_kind]]
    if platform == "cpu":
        return CPU_HOST
    raise KeyError(f"no peaks for device {device_kind!r} on platform "
                   f"{platform!r} (known TPU kinds: {sorted(_TPU_KIND_CHIP)})")


def resolve_chip(spec: "Chip | str | None" = "auto") -> Chip:
    """Coerce a chip spec to hardware constants.

    Accepts a :class:`Chip` (passes through), a registry name
    (``"tpu-v5e"``, ``"cpu-host"``), or ``"auto"``/``None`` — which
    resolves from ``jax.devices()[0]`` through :func:`chip_for_device`,
    so CI CPU numbers are never reported against TPU peaks and an unknown
    device is an error, not a default.
    """
    if isinstance(spec, Chip):
        return spec
    if spec is None or spec == "auto":
        import jax

        dev = jax.devices()[0]
        return chip_for_device(dev.platform, dev.device_kind)
    if spec in CHIPS:
        return CHIPS[spec]
    raise KeyError(f"unknown chip {spec!r} (have {sorted(CHIPS)} or 'auto')")


@dataclasses.dataclass
class RooflineTerms:
    """Per-device seconds for each roofline term; bottleneck = max."""

    compute_s: float
    memory_s: float
    collective_s: float

    @property
    def bottleneck(self) -> str:
        terms = {
            "compute": self.compute_s,
            "memory": self.memory_s,
            "collective": self.collective_s,
        }
        return max(terms, key=terms.get)

    @property
    def step_time_s(self) -> float:
        """Roofline-optimistic step time: perfectly overlapped terms."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def compute_fraction(self) -> float:
        """Fraction of roofline: 1.0 = pure compute-bound at peak."""
        t = self.step_time_s
        return self.compute_s / t if t > 0 else 0.0

    def as_dict(self) -> dict:
        return {
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "bottleneck": self.bottleneck,
            "roofline_fraction": self.compute_fraction,
        }


def terms_from_counts(
    flops_per_device: float,
    hbm_bytes_per_device: float,
    collective_bytes_per_device: float,
    *,
    dtype: str = "bf16",
    chip: Chip = V5E,
) -> RooflineTerms:
    return RooflineTerms(
        compute_s=flops_per_device / chip.peak_flops(dtype),
        memory_s=hbm_bytes_per_device / chip.hbm_bandwidth,
        collective_s=collective_bytes_per_device / chip.ici_link_bandwidth,
    )


def stencil_arithmetic_intensity(
    tile: tuple[int, int, int],
    halo: tuple[int, int, int],
    flops_per_cell: float,
    nvars_read: int,
    nvars_written: int,
    itemsize: int = 4,
) -> float:
    """FLOP/byte of one halo-expanded tile — drives tile autotuning.

    Larger tiles amortize the halo re-read; this is the TPU analogue of the
    paper's shared-memory tile-size tuning.
    """
    tx, ty, tz = tile
    hx, hy, hz = halo
    cells = tx * ty * tz
    read = (tx + 2 * hx) * (ty + 2 * hy) * (tz + 2 * hz) * nvars_read
    written = cells * nvars_written
    return (cells * flops_per_cell) / ((read + written) * itemsize)
