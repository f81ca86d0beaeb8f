"""Simulation-farm throughput: batched ensemble vs serial execution.

The farm's claim is the LM-serving claim transplanted: advancing B resident
simulations with one vmapped step costs far less than B serial steps,
because per-step dispatch and per-op overheads amortize across the slot
axis.  We measure sim-steps/sec for ensemble sizes 1/4/8/16 on the JNP
path and report speedup over running the same work serially (one
simulation at a time, the pre-farm workflow) — both sides resolved through
the ``repro.api`` front door: ``Runtime.prepare`` hands the serial jitted
step, ``Runtime.submit``/``drain`` drive the farm.

Every row reports the per-slot grid block (``slot_grid`` × ``shards_per
_slot``) so the slots × shards variant — each slot's grid decomposed over
a "shard" mesh axis — lands in ``BENCH_*.json`` directly comparable to
the undecomposed rows (same sim-steps/sec unit, explicit block size).

``--backend pallas-interpret`` runs the same matrix on the Pallas 3DBLOCK
path through the interpreter (the correctness mode, NOT a speed claim;
``--backend pallas`` needs a TPU and raises elsewhere) and emits
``BENCH_ensemble_pallas.json``: its structural fields — farm-vs-serial
parity to float32 ulps, one compiled executable per static signature, a
throughput row per ensemble size —
are gated by ``benchmarks/check_regression.py`` on every CI push, so
the farm's Pallas backend cannot silently regress to literal-baking or
per-scalar recompiles between real-hardware runs.
"""
from __future__ import annotations

import time

import numpy as np

FIELDS = ("vx", "vy", "vz", "p")


def resolve_backend(backend: str) -> str:
    """The backend the bench runs.  ``pallas`` compiles for the TPU only:
    off the TPU it raises instead of quietly measuring the interpreter
    (ask for ``pallas-interpret`` by name for that)."""
    import jax

    if backend == "pallas" and jax.default_backend() != "tpu":
        raise ValueError(
            f"backend 'pallas' needs a TPU, this host runs "
            f"{jax.default_backend()!r}; use 'pallas-interpret'")
    return backend


# 8 ulps of the O(1) lid velocity: interpret mode cannot hold farm and
# serial bitwise (XLA:CPU's fusion emitters round the interpreted grid
# loop differently once it gains a slot axis), while a wrong per-slot
# scalar row moves the fields by orders of magnitude more
PARITY_ATOL = 8 * float(np.finfo(np.float32).eps)


def _parity_check(farm_rt, serial_rt, steps: int = 6) -> dict:
    """One heterogeneous pair, farm vs serial — the structural claim of
    the scalar-table design, embedded in the artifact."""
    import jax

    sids = [farm_rt.submit("cavity", re=re, steps=steps)
            for re in (123.0, 321.0)]
    out = farm_rt.drain()
    diff = 0.0
    for sid, re in zip(sids, (123.0, 321.0)):
        pr = serial_rt.prepare("cavity", re=re)
        st = pr.state
        for _ in range(steps):
            st = pr.step(st)
        st = jax.device_get(st)
        diff = max([diff] + [float(np.abs(np.asarray(st[f])
                                          - np.asarray(out[sid].state[f])
                                          ).max()) for f in FIELDS])
    return {"max_abs_diff": diff, "bitwise": diff == 0.0,
            "ok": diff <= PARITY_ATOL}


def _bench_serial(rt, res_values, steps):
    import jax

    # warm the compile (the serial path shares one jitted step per config
    # signature via jax's own jit cache; time only the steady state)
    runs = [rt.prepare("cavity", re=float(r)) for r in res_values]
    for pr in runs:
        jax.block_until_ready(pr.step(pr.state))
    t0 = time.perf_counter()
    for pr in runs:
        st = pr.state
        for _ in range(steps):
            st = pr.step(st)
        jax.block_until_ready(st)
    return time.perf_counter() - t0


def _bench_farm(rt, res_values, steps):
    # warm: run a throwaway batch of 1 step
    for r in res_values:
        rt.submit("cavity", re=float(r), steps=1)
    rt.drain()
    sids = [rt.submit("cavity", re=float(r), steps=steps)
            for r in res_values]
    t0 = time.perf_counter()
    out = rt.drain()
    dt = time.perf_counter() - t0
    assert all(out[s].steps_done == steps for s in sids)
    return dt


def _ugrid(shape) -> str:
    from benchmarks._util import slot_grid

    return slot_grid(shape, (), None)


def _bench_decomposed(n, steps, n_slots=4, backend="jnp"):
    """Slots × shards variant: same ensemble work with each slot's grid
    decomposed over a "shard" mesh axis.  Runs at however many shards the
    host allows (1 on the single-device CI harness — the degraded fast
    path — so the row is always present and comparable)."""
    import jax

    from benchmarks._util import pick_shards, slot_grid
    from repro import api

    shards = pick_shards(jax.device_count(), n)
    decomposition = ((0, "shard"),)
    rt = api.runtime(n=n, n_slots=n_slots, jacobi_iters=20, backend=backend,
                     mesh_shape=(1, shards), mesh_axes=("slot", "shard"),
                     decomposition=decomposition)
    res = np.linspace(60.0, 400.0, n_slots)
    t = _bench_farm(rt, res, steps)
    base = rt.configure("cavity")
    return {
        "ensemble": n_slots,
        "shards_per_slot": shards,
        "slot_grid": slot_grid(base.shape, decomposition,
                               rt.mesh),
        "farm_steps_per_s": round(n_slots * steps / t, 1),
    }


def run(n: int = 16, steps: int = 80, quick: bool = False, repeats: int = 2,
        backend: str = "jnp") -> dict:
    """Ensemble members are the small/medium cases real sweeps are made of
    (UQ, parameter studies) — the regime where per-step dispatch and per-op
    overheads, not raw flops, bound serial throughput.

    ``backend`` selects the kernel template (``api.BACKENDS``); the
    Pallas variants additionally record the structural fields the CI
    regression gate pins: farm-vs-serial parity and the compile
    -cache miss count (one executable per static signature).
    """
    from repro import api
    from repro.sim import reset_compile_cache

    resolved = resolve_backend(backend)
    pallas = resolved != "jnp"
    reset_compile_cache()
    # quick trims the largest ensemble, not the measurement length: short
    # timing windows are noise-dominated and flake the >=2x gate
    batches = (1, 4, 8) if quick else (1, 4, 8, 16)
    t_start = time.time()
    rows = []
    for b in batches:
        res = np.linspace(60.0, 400.0, b)
        serial_rt = api.runtime(n=n, jacobi_iters=20, backend=resolved)
        farm_rt = api.runtime(n=n, n_slots=b, jacobi_iters=20,
                              backend=resolved)
        t_serial = min(_bench_serial(serial_rt, res, steps)
                       for _ in range(repeats))
        t_farm = min(_bench_farm(farm_rt, res, steps)
                     for _ in range(repeats))
        total = b * steps
        rows.append({
            "ensemble": b,
            # per-slot grid size: decomposed and undecomposed runs are
            # only comparable normalized to the block each device steps
            "slot_grid": _ugrid(serial_rt.configure("cavity").shape),
            "shards_per_slot": 1,
            "serial_steps_per_s": round(total / t_serial, 1),
            "farm_steps_per_s": round(total / t_farm, 1),
            "speedup": round(t_serial / t_farm, 2),
        })
    by_b = {r["ensemble"]: r for r in rows}
    # interpret mode trades speed for auditability: the farm>serial gate
    # is a hardware claim, asserted only where the kernels are compiled
    passed = (by_b[8]["speedup"] >= 2.0) if resolved != "pallas-interpret" \
        else all(r["farm_steps_per_s"] > 0 for r in rows)
    out = {
        "bench": "ensemble_farm",
        "paper_analogue": "runtime layer scheduling many generated kernels",
        "backend": backend,
        "resolved_backend": resolved,
        "grid": f"{n}x{n}x4",
        "steps_per_sim": steps,
        "batches": rows,
        "decomposed": _bench_decomposed(n, steps, backend=resolved),
        "speedup_at_8": by_b[8]["speedup"],
        "passed": passed,
        "wall_s": round(time.time() - t_start, 1),
    }
    if pallas:
        # structural fields the regression gate pins (host-independent):
        # each undecomposed farm is one static signature (one miss per
        # ensemble size), the decomposed variant adds one more; the
        # parity farm below re-hits the n_slots=4 signature
        expected = len(batches) + 1
        parity_rt = api.runtime(n=n, n_slots=4, jacobi_iters=20,
                                backend=resolved)
        serial_rt = api.runtime(n=n, jacobi_iters=20, backend=resolved)
        out["parity"] = _parity_check(parity_rt, serial_rt)
        out["expected_compile_misses"] = expected
        out["compile_cache"] = api.compile_cache_stats()
        out["passed"] = bool(
            out["passed"] and out["parity"]["ok"]
            and out["compile_cache"]["misses"] == expected)
    return out


def main(argv=None) -> int:
    import argparse
    import json

    ap = argparse.ArgumentParser()
    ap.add_argument("--backend", default="jnp",
                    help="kernel backend (api.BACKENDS); 'pallas' "
                         "needs a TPU")
    ap.add_argument("--n", type=int, default=16)
    ap.add_argument("--steps", type=int, default=80)
    ap.add_argument("--repeats", type=int, default=2)
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--out-dir", default=None,
                    help="write BENCH_ensemble[_pallas].json (repro.bench"
                         ".v1 envelope) here instead of printing raw JSON")
    args = ap.parse_args(argv)

    res = run(n=args.n, steps=args.steps, quick=args.quick,
              repeats=args.repeats, backend=args.backend)
    if args.out_dir is None:
        print(json.dumps(res, indent=1))
        return 0 if res["passed"] else 1

    from repro import obs

    name = "ensemble" if res["resolved_backend"] == "jnp" \
        else "ensemble_pallas"
    doc = obs.make_bench_doc(
        name, {k: v for k, v in res.items() if k not in ("passed", "wall_s")},
        passed=bool(res["passed"]), wall_s=res["wall_s"])
    path = obs.write_bench(doc, args.out_dir)
    obs.load_bench(path)   # round-trip: the artifact on disk validates
    print(f"[benchmarks] {name} -> {path} "
          f"(passed={doc['passed']}, {doc['wall_s']}s)")
    return 0 if doc["passed"] else 1


if __name__ == "__main__":
    import sys

    sys.exit(main())
