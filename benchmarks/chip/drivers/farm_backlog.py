"""Driver ``farm_backlog``: a parameter sweep with more work than the window.

Set-up submits ``members`` cavity members of ``steps`` steps each through
``Runtime.submit``, their Reynolds numbers in an order drawn from the seed,
and compiles the farm's programs without stepping any member.  The window
calls ``SimulationService.run(budget)`` until the time is up; each call
admits a wave, steps it and harvests it.  Equal lengths make each wave one
chunk of ``steps`` device steps.
"""
from __future__ import annotations

import time
import types

import jax
import numpy as np

import farm_common


def setup(cell):
    cfg, tr = cell.config, cell.traffic
    rng = np.random.default_rng(cell.seed)
    rt = farm_common.runtime(cfg)
    res = farm_common.re_sequence(cfg, tr["members"], rng)
    sids = [farm_common.submit(rt, cfg, re, tr["steps"], tag=str(i))
            for i, re in enumerate(res)]
    (svc,) = rt.services()
    farm_common.warm(svc)
    return types.SimpleNamespace(cell=cell, rt=rt, svc=svc, rng=rng,
                                 members=dict(zip(sids, res)))


def window(run, seconds: float, span) -> dict:
    svc, budget = run.svc, run.cell.traffic["budget"]
    farm = svc.farm
    jax.block_until_ready(farm.exec.state)
    steps0, dev0 = farm_common.member_steps(farm), farm.device_steps
    t0 = time.perf_counter()
    deadline = t0 + seconds
    while True:
        with span("bench.service_run"):
            svc.run(budget)
        if time.perf_counter() >= deadline:
            break
    with span("bench.block"):
        jax.block_until_ready(farm.exec.state)
    window_s = time.perf_counter() - t0
    member_steps = farm_common.member_steps(farm) - steps0
    cells = farm_common.cells_per_member(run.cell.config)
    status = {sid: run.rt.poll(sid)["status"] for sid in run.members}
    done = {sid: run.rt.result(sid, block=False)
            for sid, st in status.items() if st == "done"}
    failed = sum(st in ("failed", "diverged") for st in status.values())
    k = run.cell.config["check"]["members"]
    run.sampled = [(run.members[s], r.steps_done, r.state) for s, r in
                   _sample(done, k, run.rng)]
    return {"window_s": window_s,
            "attempted": len(done) + failed + farm.table.n_active,
            "failed": failed, "wrong": failed,
            "device_steps": farm.device_steps - dev0,
            "step_program": farm_common.STEP_PROGRAM,
            "cells_per_device_step": cells * run.cell.config["slots"],
            "queued_at_end": farm.table.n_queued,
            "end_to_end": {"cell_updates_per_s":
                           cells * member_steps / window_s / 1e6}}


def _sample(done: dict, k: int, rng) -> list:
    ok = [(r.steps_done, (sid, r)) for sid, r in sorted(done.items())
          if r.terminated == "steps"]
    return farm_common.sample(ok, k, rng)


def release(run) -> None:
    run.svc = run.rt = None


def check(run) -> list:
    return farm_common.check(run)


def control(cell) -> list:
    """The control over the members a run compares: the first admitted."""
    cfg, tr = cell.config, cell.traffic
    res = farm_common.re_sequence(cfg, tr["members"],
                                  np.random.default_rng(cell.seed))
    k = cfg["check"]["members"]
    return farm_common.control(cell, [(re, tr["steps"]) for re in res[:k]])
