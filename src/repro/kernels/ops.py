"""jit'd public wrappers around the kernels in this package.

Each op dispatches between the fused-jnp template (the XLA path, the default
on every backend) and the Pallas 3DBLOCK template (asked for explicitly;
interpret mode for CPU validation).  The CFD solver calls these — never
``pallas_call`` directly.
"""
from __future__ import annotations

import functools

import jax.numpy as jnp

from repro.core.generator import generate
from repro.kernels import stencil3d


# JNP until tests/test_chip_compile.py::test_3dblock_jacobi_compiles passes
DEFAULT_TEMPLATE = "JNP"


@functools.lru_cache(maxsize=None)
def _kernel(name: str, template: str, interpret: bool, tile: tuple | None):
    desc = stencil3d.DESCRIPTORS[name]
    if tile is not None:
        import dataclasses

        desc = dataclasses.replace(desc, tile=tile)
    return generate(desc, stencil3d.BODIES[name], template=template,
                    interpret=interpret)


def _auto_tile(name: str, arrays: dict) -> tuple:
    """Roofline-autotuned tile for this kernel's local interior.

    Resolved from the (vmap-invisible) local array shapes, so the farm's
    slot-batched call and a serial run of the same grid tune identically —
    the memoized choice lives in ``autotune._TILE_CACHE`` and feeds the
    ``_kernel`` compile-cache key.
    """
    from repro.core import autotune

    desc = stencil3d.DESCRIPTORS[name]
    first = arrays[desc.inputs[0]]
    space = tuple(first.shape)
    if desc.inputs[0] in desc.cached_inputs:
        space = tuple(s - lo - hi for s, lo, hi in
                      zip(space, desc.halo_lo, desc.halo_hi))
    itemsize = jnp.dtype(first.dtype).itemsize
    return autotune.tile_for(desc, space, itemsize=itemsize).tile


def apply_kernel(name: str, arrays: dict, *, template: str | None = None,
                 interpret: bool = False, tile: tuple | str | None = None,
                 **params):
    """Run one descriptor kernel. ``tile`` overrides the descriptor TILE:
    a concrete 3-tuple, or ``"auto"`` for the chip-aware roofline choice
    (ignored on the JNP template, which has no tiles)."""
    tmpl = template or DEFAULT_TEMPLATE
    if tile == "auto":
        tile = _auto_tile(name, arrays) if tmpl == "3DBLOCK" else None
    return _kernel(name, tmpl, interpret, tile)(arrays, **params)


# -- convenience wrappers (the public op surface) ---------------------------
def update_velocity(vx, vy, vz, *, dt, h, nu, fx=0.0, fy=0.0, fz=0.0, **kw):
    out = apply_kernel(
        "UPDATE_VELOCITY", {"vx": vx, "vy": vy, "vz": vz},
        dt=dt, h=h, nu=nu, fx=fx, fy=fy, fz=fz, **kw)
    return out["vx"], out["vy"], out["vz"]


def divergence(vx, vy, vz, *, h, **kw):
    return apply_kernel("DIVERGENCE", {"vx": vx, "vy": vy, "vz": vz}, h=h, **kw)["div"]


def jacobi_pressure(p, rhs, *, h, omega=1.0, **kw):
    return apply_kernel("JACOBI_PRESSURE", {"p": p, "rhs": rhs},
                        h=h, omega=omega, **kw)["p"]


def project_velocity(vx, vy, vz, p, *, dt, h, **kw):
    out = apply_kernel(
        "PROJECT_VELOCITY", {"vx": vx, "vy": vy, "vz": vz, "p": p},
        dt=dt, h=h, **kw)
    return out["vx"], out["vy"], out["vz"]


def advect_diffuse_scalar(T, vx, vy, vz, *, dt, h, kappa, **kw):
    return apply_kernel(
        "ADVECT_DIFFUSE_SCALAR", {"T": T, "vx": vx, "vy": vy, "vz": vz},
        dt=dt, h=h, kappa=kappa, **kw)["T"]
