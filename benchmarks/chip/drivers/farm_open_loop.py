"""Driver ``farm_open_loop``: a served farm under Poisson arrivals.

Arrivals and member lengths come from the traffic file: the inter-arrival
gaps (exponential, mean 1 / ``rate_per_s``) and the lengths (log-uniform
in [``steps_min``, ``steps_max``]) are one fixed set drawn from
``base_seed``, scaled so that the arrivals fill the window; the run's seed
only orders them, so every seed offers the same work.  The loop submits
every arrival that is due, then calls ``SimulationService.run(budget)``,
and repeats; with nothing to step it sleeps until the next arrival.

A member's latency runs from its scheduled arrival to the moment the loop
sees its result.  The latency set is the members due in the first
``latency_share`` of the window.  Nothing is stepped after the window: a
member of the set unfinished when the window ends counts as failed and as
missing, with the wait up to the window's end as its latency.  The check
compares a sample of the members that finished in the window.
"""
from __future__ import annotations

import math
import time
import types

import jax
import numpy as np

import farm_common


def arrivals(traffic: dict, seconds: float, seed: int):
    """(times, steps) of the arrivals of one window, times in seconds from
    its start; the set is fixed by ``base_seed``, the order by ``seed``."""
    n = max(int(round(traffic["rate_per_s"] * seconds)), 1)
    base = np.random.default_rng(traffic["base_seed"])
    gaps = base.exponential(1.0, size=n)
    lo, hi = math.log(traffic["steps_min"]), math.log(traffic["steps_max"])
    steps = np.clip(np.rint(np.exp(base.uniform(lo, hi, size=n))),
                    traffic["steps_min"], traffic["steps_max"]).astype(int)
    rng = np.random.default_rng(seed)
    gaps = rng.permutation(gaps)
    steps = rng.permutation(steps)
    times = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    times *= seconds / gaps.sum()
    return times, steps, rng


def setup(cell):
    cfg = cell.config
    times, steps, rng = arrivals(cell.traffic, cell.seconds, cell.seed)
    res = farm_common.re_sequence(cfg, len(times), rng)
    rt = farm_common.runtime(cfg)
    # the service exists once its signature has been submitted to: one
    # member of one step, driven to its end, also warms every program
    farm_common.submit(rt, cfg, res[0], 1, tag="warm-up")
    (svc,) = rt.services()
    farm_common.warm(svc)
    svc.run(1)
    jax.block_until_ready(svc.farm.exec.state)
    return types.SimpleNamespace(cell=cell, rt=rt, svc=svc, rng=rng,
                                 times=times, steps=steps, res=res,
                                 seen=len(svc.farm.results), finish={},
                                 status={})


def _collect(run) -> None:
    """Stamp the members whose results appeared since the last look."""
    results = run.svc.farm.results
    keys = list(results)
    now = time.perf_counter()
    for key in keys[run.seen:]:
        r = results[key]
        if r.tag.isdigit():
            run.finish[int(r.tag)] = now
            run.status[int(r.tag)] = r
    run.seen = len(keys)


def window(run, seconds: float, span) -> dict:
    cfg, budget = run.cell.config, run.cell.traffic["budget"]
    svc = run.svc
    farm = svc.farm
    jax.block_until_ready(farm.exec.state)
    dev0 = farm.device_steps
    t0 = time.perf_counter()
    due, n, lags = 0, len(run.times), []
    while True:
        now = time.perf_counter()
        if now - t0 >= seconds:
            break
        while due < n and run.times[due] <= now - t0:
            with span("bench.submit"):
                farm_common.submit(run.rt, cfg, run.res[due],
                                   run.steps[due], tag=str(due))
            lags.append(now - t0 - run.times[due])
            due += 1
        if farm.table.n_active or farm.table.n_queued:
            with span("bench.service_run"):
                svc.run(budget)
            _collect(run)
        else:
            nxt = run.times[due] if due < n else seconds
            with span("bench.idle"):
                time.sleep(max(0.0, t0 + min(nxt, seconds)
                               - time.perf_counter()))
    with span("bench.block"):
        jax.block_until_ready(farm.exec.state)
    t1 = time.perf_counter()
    _collect(run)
    tr = run.cell.traffic
    wanted = [i for i in range(due)
              if run.times[i] < tr["latency_share"] * seconds]
    missing = [i for i in wanted if i not in run.finish]
    lat = [run.finish.get(i, t1) - (t0 + run.times[i]) for i in wanted]
    wrong = sum(r.terminated != "steps" for r in run.status.values())
    done = [(r.steps_done, (i, r)) for i, r in sorted(run.status.items())
            if r.terminated == "steps"]
    run.sampled = [(run.res[i], r.steps_done, r.state) for i, r in
                   farm_common.sample(done, cfg["check"]["members"], run.rng)]
    return {"window_s": t1 - t0, "attempted": len(wanted),
            "failed": len(missing) + wrong, "wrong": wrong,
            "latency_set": len(wanted), "missing": len(missing),
            "device_steps": farm.device_steps - dev0,
            "step_program": farm_common.STEP_PROGRAM,
            "cells_per_device_step": farm_common.cells_per_member(cfg)
            * cfg["slots"],
            "submitted": due, "queued_at_end": farm.table.n_queued,
            "generator_lag_max_s": max(lags) if lags else 0.0,
            "end_to_end": {"member_latency_p95_s": nearest_rank(lat, 0.95)}}


def nearest_rank(values: list, q: float) -> float:
    """The ``q`` quantile of ``values`` by the nearest-rank rule."""
    if not values:
        return math.inf
    ordered = sorted(values)
    return ordered[max(math.ceil(q * len(ordered)) - 1, 0)]


def release(run) -> None:
    run.svc = run.rt = None


def check(run) -> list:
    return farm_common.check(run)


def control(cell) -> list:
    """The control over members of the run's lengths, the longest among
    them, drawn as a run draws its sample."""
    cfg = cell.config
    times, steps, rng = arrivals(cell.traffic, cell.seconds, cell.seed)
    res = farm_common.re_sequence(cfg, len(times), rng)
    members = [(int(s), (re, int(s))) for re, s in zip(res, steps)]
    return farm_common.control(
        cell, farm_common.sample(members, cfg["check"]["members"], rng))
