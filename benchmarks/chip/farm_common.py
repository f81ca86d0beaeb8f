"""What the farm drivers share: the runtime, members, warm-up and check.

A farm cell drives ``repro.api.Runtime.submit`` and the one
``SimulationService`` it builds for the configuration's signature.  Its
answers are the finished members' fields; the check steps a sample of them
from rest with :mod:`reference` and compares.
"""
from __future__ import annotations

import numpy as np

import reference

STATE_FIELDS = reference.FIELDS
# the ensemble step, as its compiled module is named in a profiler trace
STEP_PROGRAM = "run_k"


def runtime(cfg: dict):
    from repro import api

    nx, _, nz = cfg["grid"]
    return api.runtime(n=nx, nz=nz, n_slots=cfg["slots"],
                       backend=cfg["backend"])


def submit(rt, cfg: dict, re: float, steps: int, tag: str) -> int:
    """Queue one member through the runtime's front door, with the dt and
    the sweeps the configuration states."""
    return rt.submit(cfg["scenario"], re=float(re), steps=int(steps),
                     tag=tag, dt=cfg["dt"][str(int(re))],
                     jacobi_iters=cfg["jacobi_iters"],
                     lid_velocity=cfg["lid_velocity"])


def warm(svc) -> None:
    """Compile what the window will run, without stepping any member: the
    ensemble step (a chunk of 0 steps), one admission and one harvest."""
    import jax
    from repro.cfd.ns3d import params_from_config

    ex = svc.farm.exec
    ex.step_many(0)
    ex.read_slot(0)
    ex.write_slot(0, params_from_config(svc.farm.base_config))
    ex.clear_slot(0)
    jax.block_until_ready(ex.state)


def member_steps(farm) -> int:
    """Member-steps done so far: finished members and resident ones."""
    done = sum(r.steps_done for r in farm.results.values())
    return done + sum(e.steps_done for _, e in farm.table.occupied())


def cells_per_member(cfg: dict) -> int:
    return int(np.prod(cfg["grid"]))


def re_sequence(cfg: dict, count: int, rng) -> np.ndarray:
    """``count`` Reynolds numbers, the configuration's values in equal
    shares, in an order drawn from ``rng``."""
    values = np.resize(np.asarray(cfg["re_values"], np.float64), count)
    return rng.permutation(values)


def sample(finished: list, k: int, rng) -> list:
    """Up to ``k`` finished members drawn from ``rng``, the longest among
    them.  ``finished`` holds (steps, member) pairs."""
    if len(finished) <= k:
        return [m for _, m in finished]
    longest = max(range(len(finished)), key=lambda i: finished[i][0])
    rest = [i for i in range(len(finished)) if i != longest]
    picked = [longest] + list(rng.choice(rest, size=k - 1, replace=False))
    return [finished[i][1] for i in picked]


def longest(traffic: dict) -> int:
    """The most steps a member of this traffic can have."""
    return int(traffic.get("steps_max", traffic.get("steps", 0)))


def reference_members(cfg: dict, members: list, dtype, last: int) -> list:
    """The reference's final fields (float32 numpy) of each (re, steps)
    member, stepped from rest in ``dtype``; ``last`` bounds the steps."""
    import jax.numpy as jnp

    res = [re for re, _ in members]
    out = reference.evolve_members(
        [1.0 / re for re in res], [cfg["dt"][str(int(re))] for re in res],
        [steps for _, steps in members], shape=tuple(cfg["grid"]),
        h=cfg["extent"] / cfg["grid"][0], lid=cfg["lid_velocity"],
        sweeps=cfg["jacobi_iters"], dtype=dtype, last=last)
    out = {f: np.asarray(out[f].astype(jnp.float32)) for f in STATE_FIELDS}
    return [{f: out[f][i] for f in STATE_FIELDS} for i in range(len(res))]


def member_gaps(cfg: dict, members: list, last: int) -> list:
    """Each member's widest gap to the float32 reference.  ``members``
    holds (re, steps, host state) triples."""
    if not members:
        return []
    want = reference_members(cfg, [(re, s) for re, s, _ in members],
                             np.float32, last)
    return [reference.field_gap(state, w)
            for (_, _, state), w in zip(members, want)]


def _compare(cfg: dict, gaps: list) -> list:
    """The farm cells' number: the widest gap over the compared members."""
    worst = max(gaps) if gaps else float("inf")
    return [("member_gap", worst, cfg["check"]["limits"]["member_gap"])]


def check(run) -> list:
    cfg = run.cell.config
    return _compare(cfg, member_gaps(cfg, run.sampled,
                                     longest(run.cell.traffic)))


def control(cell, members: list) -> list:
    """The check's number with the reference computed in bfloat16, the
    precision below the configuration's float32, in the program's place,
    over the (re, steps) ``members`` a run of the cell would compare."""
    import jax.numpy as jnp

    cfg, last = cell.config, longest(cell.traffic)
    want = reference_members(cfg, members, jnp.float32, last)
    got = reference_members(cfg, members, jnp.bfloat16, last)
    return _compare(cfg, [reference.field_gap(g, w)
                          for g, w in zip(got, want)])
