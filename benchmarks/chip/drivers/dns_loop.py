"""Driver ``dns_loop``: one DNS stepped back to back for the whole window.

Set-up resolves the run through ``Runtime.prepare``, puts the seed's
Taylor-Green vortex in place of the initial fields, and takes the first
``check.steps`` steps through the window's own call.  The window then steps
``PreparedRun.step`` with ``Runtime.run``'s policy: dispatch step i, then
wait for step i-1.  The check steps the same initial fields with the
reference and compares the state after those first steps; the control puts
the reference in bfloat16 in the program's place.
"""
from __future__ import annotations

import math
import time
import types

import jax
import jax.numpy as jnp
import numpy as np

import reference

FIELDS = reference.FIELDS
# the solver's step, as its compiled module is named in a profiler trace
STEP_PROGRAM = "_step_local"


def _runtime(cfg: dict):
    from repro import api

    nx, _, nz = cfg["grid"]
    kw = dict(n=nx, nz=nz, backend=cfg["backend"])
    mesh = cfg.get("mesh")
    if mesh:
        kw.update(mesh_shape=tuple(mesh["shape"]),
                  mesh_axes=tuple(mesh["axes"]),
                  decomposition=tuple(tuple(d) for d in mesh["decomposition"]))
    return api.runtime(**kw)


def phase_of(seed: int) -> np.ndarray:
    return np.random.default_rng(seed).uniform(0.0, 2 * math.pi, size=2)


def initial_fields(cfg: dict, phase, sharding, dtype):
    """The seed's vortex, made on the device(s) in ``sharding``."""
    shape, extent = tuple(cfg["grid"]), cfg["extent"]
    make = jax.jit(lambda ph: reference.taylor_green_fields(
        shape, extent, ph, dtype), out_shardings=sharding)
    return make(np.asarray(phase, np.float32))


def setup(cell):
    cfg = cell.config
    rt = _runtime(cfg)
    pr = rt.prepare(cfg["scenario"], nu=cfg["nu"], dt=cfg["dt"],
                    jacobi_iters=cfg["jacobi_iters"])
    phase = phase_of(cell.seed)
    state = {k: v for k, v in pr.state.items() if k.startswith("mask_")}
    pr.state = None
    state.update(initial_fields(cfg, phase, state["mask_vx"].sharding,
                                np.float32))
    for _ in range(cfg["check"]["steps"]):
        state, last = pr.step(state), state
        jax.block_until_ready(last)
    jax.block_until_ready(state)
    kept = jax.device_get({f: state[f] for f in FIELDS})
    return types.SimpleNamespace(cell=cell, rt=rt, pr=pr, state=state,
                                 phase=phase, kept=kept)


def window(run, seconds: float, span) -> dict:
    pr, state = run.pr, run.state
    jax.block_until_ready(state)
    t0 = time.perf_counter()
    deadline = t0 + seconds
    steps = 0
    while True:
        with span("bench.step"):
            nxt = pr.step(state)
        with span("bench.block"):
            jax.block_until_ready(state)
        state = nxt
        steps += 1
        if time.perf_counter() >= deadline:
            break
    with span("bench.block"):
        jax.block_until_ready(state)
    window_s = time.perf_counter() - t0
    run.state = state
    cells = math.prod(run.cell.config["grid"])
    return {"window_s": window_s, "attempted": steps, "failed": 0,
            "device_steps": steps, "cells_per_device_step": cells,
            "step_program": STEP_PROGRAM,
            "end_to_end": {"cell_updates_per_s": cells * steps / window_s
                           / 1e6}}


def release(run) -> None:
    run.state = run.pr = run.rt = None


def reference_state(cfg: dict, phase, devices, dtype):
    """The reference's state after ``check.steps`` steps, on ``devices``:
    one chip, or split in x over them as the configuration's mesh is."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    steps = cfg["check"]["steps"]
    kw = dict(case="taylor_green", h=cfg["extent"] / cfg["grid"][0],
              dt=cfg["dt"], nu=cfg["nu"], lid=0.0,
              sweeps=cfg["jacobi_iters"], masks=None)
    if not cfg.get("mesh"):
        ic = initial_fields(cfg, phase, None, dtype)
        with jax.default_matmul_precision("highest"):
            return jax.jit(lambda s: reference.evolve(s, steps, **kw))(ic)
    mesh = Mesh(np.asarray(devices), ("x",))
    spec = P("x")
    ic = initial_fields(cfg, phase, NamedSharding(mesh, spec), dtype)
    body = jax.shard_map(
        lambda s: reference.evolve(s, steps, ring="x", **kw), mesh=mesh,
        in_specs=({f: spec for f in FIELDS},),
        out_specs={f: spec for f in FIELDS}, check_vma=False)
    with jax.default_matmul_precision("highest"):
        return jax.jit(body)(ic)


def _compare(cfg: dict, got: dict, want: dict) -> list:
    return [("state_gap", reference.device_gap(got, want),
             cfg["check"]["limits"]["state_gap"])]


def check(run) -> list:
    cfg = run.cell.config
    want = reference_state(cfg, run.phase, run.cell.devices, np.float32)
    got = {f: jax.device_put(run.kept[f], want[f].sharding) for f in FIELDS}
    return _compare(cfg, got, want)


def control(cell) -> list:
    """The check's numbers with the reference computed in bfloat16, the
    precision below the configuration's float32, in the program's place."""
    cfg, phase = cell.config, phase_of(cell.seed)
    want = reference_state(cfg, phase, cell.devices, jnp.float32)
    got = reference_state(cfg, phase, cell.devices, jnp.bfloat16)
    return _compare(cfg, got, want)
