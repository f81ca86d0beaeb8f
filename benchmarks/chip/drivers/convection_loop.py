"""Driver ``convection_loop``: one Rayleigh-Benard DNS stepped back to back
for the whole window.

Set-up resolves the run through ``Runtime.prepare("rayleigh_benard", ...)``
at the configuration's grid (depth ``grid[0]``, width ``grid[1]`` and
``grid[2]``: the aspect ratio is ``grid[1] // grid[0]``), puts the seed's
perturbed conductive state (``reference_convection.initial_fields``) in
place of the initial fields, and takes the first ``check.steps`` steps
through the window's own call.  The window and the release are
``dns_loop``'s: dispatch step i, then wait for step i-1.  The check steps
the same initial fields with the reference and compares the state after
those first steps; the control puts the reference in bfloat16 in the
program's place.
"""
from __future__ import annotations

import types

import jax
import jax.numpy as jnp
import numpy as np

import reference
import reference_convection as rc
from drivers.dns_loop import release, window  # noqa: F401

FIELDS = rc.FIELDS


def _shape(cfg: dict) -> tuple:
    return tuple(cfg["grid"])


def _runtime(cfg: dict):
    from repro import api

    nx, ny, nz = _shape(cfg)
    return api.runtime(n=ny, nz=nz, backend=cfg["backend"])


def initial_fields(cfg: dict, seed: int, sharding, dtype):
    """The seed's perturbed conductive state, made on the device."""
    shape = _shape(cfg)
    make = jax.jit(lambda: rc.initial_fields(
        shape, 1.0 / shape[0], seed, cfg["amplitude"], dtype),
        out_shardings=sharding)
    return make()


def setup(cell):
    cfg = cell.config
    nx, ny, _ = _shape(cfg)
    rt = _runtime(cfg)
    pr = rt.prepare(cfg["scenario"], aspect=ny // nx, ra=cfg["ra"],
                    pr=cfg["pr"], dt=cfg["dt"],
                    jacobi_iters=cfg["jacobi_iters"])
    if pr.config.shape != _shape(cfg):
        raise ValueError(f"the runtime resolved {pr.config.shape}, the "
                         f"configuration asks for {_shape(cfg)}")
    state = {k: v for k, v in pr.state.items() if k.startswith("mask_")}
    pr.state = None
    state.update(initial_fields(cfg, cell.seed, state["mask_vx"].sharding,
                                np.float32))
    for _ in range(cfg["check"]["steps"]):
        state, last = pr.step(state), state
        jax.block_until_ready(last)
    jax.block_until_ready(state)
    kept = jax.device_get({f: state[f] for f in FIELDS})
    return types.SimpleNamespace(cell=cell, rt=rt, pr=pr, state=state,
                                 kept=kept)


def reference_state(cfg: dict, seed: int, dtype):
    """The reference's state after ``check.steps`` steps."""
    nu, kappa = rc.viscosities(cfg["ra"], cfg["pr"])
    kw = dict(h=1.0 / cfg["grid"][0], dt=cfg["dt"], nu=nu, kappa=kappa,
              sweeps=cfg["jacobi_iters"])
    ic = initial_fields(cfg, seed, None, dtype)
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda s: rc.evolve(s, cfg["check"]["steps"], **kw))(ic)


def _compare(cfg: dict, got: dict, want: dict) -> list:
    limits = cfg["check"]["limits"]
    return [("state_gap", reference.device_gap(got, want),
             limits["state_gap"]),
            ("temperature_gap", rc.temperature_gap(got, want),
             limits["temperature_gap"])]


def check(run) -> list:
    cfg = run.cell.config
    want = reference_state(cfg, run.cell.seed, np.float32)
    got = {f: jax.device_put(run.kept[f], want[f].sharding) for f in FIELDS}
    return _compare(cfg, got, want)


def control(cell) -> list:
    """The check's numbers with the reference computed in bfloat16, the
    precision below the configuration's float32, in the program's place."""
    cfg = cell.config
    want = reference_state(cfg, cell.seed, jnp.float32)
    got = reference_state(cfg, cell.seed, jnp.bfloat16)
    return _compare(cfg, got, want)
