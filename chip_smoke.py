#!/usr/bin/env python3
"""Run the CFD main path once on a TPU and check what it computes.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # the paths that span four chips

One chip, through ``repro.api`` as a user drives it:

1. device check: ``jax.devices()[0]`` must be a TPU, or nothing runs;
2. kernels: the four ns3d stage kernels at 256^3 on the chip against the
   plain jnp references of ``repro.kernels.ref``;
3. serial DNS: Taylor-Green at 256^3 (the jnp backend) for
   ``DNS_STEPS`` steps — error against the analytic decay, max
   divergence, compile and steady seconds — and the same steps on the
   host's CPU backend in this process, field by field;
4. farm: eight lid-driven cavity members at 128x128x4 (Ghia, Ghia & Shin's
   seven Reynolds numbers and a second Re 100) through ``submit``/``drain``;
   every member must finish its steps, and the Re 100 members must agree
   with a serial ``run`` of the same member;
5. memory: the device's peak bytes in use after each phase.

``--four-chips`` runs only what spans chips: Taylor-Green at 256^3 with x
split over a 4-way ``shard`` mesh against the same run on one chip, and a
(2, 2) ``("slot", "shard")`` cavity farm against the one-device farm, with
each chip's peak bytes.

Every check that fails exits non-zero.  On success the last line of
standard output is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import collections
import json
import math
import os
import sys
import time

import numpy as np

FIELDS = ("vx", "vy", "vz", "p")
DNS_N, DNS_STEPS = 256, 24
FARM_N, FARM_SLOTS, FARM_STEPS = 128, 8, 128
# Ghia, Ghia & Shin (1982): Re 100 to 10000, plus a second Re 100 member
FARM_RE = (100.0, 400.0, 1000.0, 3200.0, 5000.0, 7500.0, 10000.0, 100.0)

EPS = float(np.finfo(np.float32).eps)
# Float32 drift allowed per step, in ulps of the field's largest magnitude.
# Two backends (or a vmapped and a plain program) round differently: FMA
# contraction, fusion order, sin/cos of the initial condition.  A velocity
# carries such a difference forward about unchanged.  The pressure is
# re-solved each step from div(u*)/dt, which scales a ulp-level change of u
# by about h/dt; on the CPU at 64^3 a 2-ulp change of the initial velocity
# moved u by 2 ulps and p by about 30 ulps, flat over 48 steps.
ULPS_PER_STEP = {"vx": 4, "vy": 4, "vz": 4, "p": 32}
# one kernel application against its reference: a handful of roundings
KERNEL_ULPS = 8
# decomposed against single-device diagnostics (tests/test_cfd.py)
DIAG_TOL = 1e-5


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str):
    if not ok:
        raise SmokeFailure(what)


def drift(got: dict, want: dict, steps: int, label: str) -> bool:
    """Compare fields to the per-step ulp allowance; print each field's
    drift in ulps.  Returns whether all fields are bitwise equal."""
    bitwise = True
    for f in FIELDS:
        g, w = np.asarray(got[f]), np.asarray(want[f])
        check(g.shape == w.shape and np.all(np.isfinite(g)),
              f"{label}: {f} has shape {g.shape} or non-finite values")
        scale = float(np.abs(w).max())
        diff = float(np.abs(g - w).max())
        bound = ULPS_PER_STEP[f] * (steps + 1) * EPS * scale
        ulps = diff / (EPS * scale) if scale else 0.0
        print(f"  {label} {f}: max|diff| {diff:.3e} = {ulps:.2f} ulps "
              f"of {scale:.4g} (bound {bound:.3e})")
        check(diff <= bound, f"{label}: {f} drifted {diff:.3e} > {bound:.3e}")
        bitwise &= diff == 0.0
    return bitwise


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------
def check_kernels(n: int = DNS_N):
    """The four stage kernels (default template) against kernels/ref.py."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops, ref

    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    u = [jax.random.normal(k, (n, n, n), jnp.float32) for k in keys]
    wrap = [jnp.pad(x, 1, mode="wrap") for x in u]
    lo = [jnp.pad(x, ((1, 0),) * 3, mode="wrap") for x in u[:3]]
    hi = jnp.pad(u[3], ((0, 1),) * 3, mode="wrap")
    h, dt, nu = 2 * math.pi / n, 2e-4, 0.1
    cases = {
        "update_velocity": (
            lambda a, b, c: ops.update_velocity(a, b, c, dt=dt, h=h, nu=nu),
            lambda a, b, c: ref.update_velocity(a, b, c, dt=dt, h=h, nu=nu),
            wrap[:3]),
        "divergence": (lambda a, b, c: ops.divergence(a, b, c, h=h),
                       lambda a, b, c: ref.divergence(a, b, c, h=h), lo),
        "jacobi_pressure": (lambda p, r: ops.jacobi_pressure(p, r, h=h),
                            lambda p, r: ref.jacobi_pressure(p, r, h=h),
                            [wrap[3], u[0]]),
        "project_velocity": (
            lambda a, b, c, p: ops.project_velocity(a, b, c, p, dt=dt, h=h),
            lambda a, b, c, p: ref.project_velocity(a, b, c, p, dt=dt, h=h),
            u[:3] + [hi]),
    }
    print(f"kernels at {n}^3 vs kernels/ref.py:")
    for name, (op, oracle, args) in cases.items():
        got = jax.tree.leaves(jax.jit(op)(*args))
        want = jax.tree.leaves(jax.jit(oracle)(*args))
        worst = 0.0
        for g, w in zip(got, want):
            g, w = np.asarray(g), np.asarray(w)
            scale = float(np.abs(w).max())
            worst = max(worst, float(np.abs(g - w).max()) / (EPS * scale))
        print(f"  {name}: {worst:.2f} ulps (bound {KERNEL_ULPS})")
        check(worst <= KERNEL_ULPS, f"kernel {name}: {worst:.2f} ulps")


def taylor_green(rt, steps: int) -> dict:
    """Taylor-Green through ``Runtime.prepare``: fields, diagnostics and
    the first call's and the steady steps' seconds."""
    import jax

    pr = rt.prepare("taylor_green")
    t0 = time.perf_counter()
    state = jax.block_until_ready(pr.step(pr.state))
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(steps - 1):
        state = pr.step(state)
    state = jax.block_until_ready(state)
    step_s = (time.perf_counter() - t0) / max(steps - 1, 1)
    diag = pr.analyze(state, steps)
    health = pr.solver.health_report(state)
    return {"state": {f: np.asarray(jax.device_get(state[f]))
                      for f in FIELDS},
            "err_vx": diag["analytic_error"]["err_vx"],
            "err_vy": diag["analytic_error"]["err_vy"],
            "energy": diag["kinetic_energy"],
            "div_max": health["div_linf"],
            "compile_s": first - step_s, "step_s": step_s}


def report_dns(label: str, r: dict):
    print(f"{label}: err_vx {r['err_vx']:.3e} err_vy {r['err_vy']:.3e} "
          f"div_max {r['div_max']:.3e} energy {r['energy']:.9g} "
          f"compile_s {r['compile_s']:.2f} step_s {r['step_s']:.5f}")
    check(r["err_vx"] < 5e-3 and r["err_vy"] < 5e-3,
          f"{label}: error against the analytic decay too large")
    check(math.isfinite(r["div_max"]), f"{label}: divergence not finite")


def farm(rt, steps: int) -> dict:
    """Submit the Reynolds sweep, drain, check every member finished."""
    t0 = time.perf_counter()
    sids = [rt.submit("cavity", re=re, steps=steps, tag=f"re{re:g}-{i}")
            for i, re in enumerate(FARM_RE)]
    out = rt.drain()
    wall = time.perf_counter() - t0
    results = [out[s] for s in sids]
    for r in results:
        check(r.terminated == "steps" and r.steps_done == steps,
              f"farm member {r.tag}: {r.terminated} after {r.steps_done} "
              f"steps ({r.error})")
        check(all(np.all(np.isfinite(r.state[f])) for f in FIELDS),
              f"farm member {r.tag}: non-finite fields")
    print(f"farm: {len(results)} members x {steps} steps, all "
          f"terminated=steps, {wall:.2f} s wall (compile included)")
    return {r.tag: r for r in results}


def peak_bytes(devices) -> list:
    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in devices]


# ---------------------------------------------------------------------------
# runs
# ---------------------------------------------------------------------------
def run_one_chip(dns_n=DNS_N, dns_steps=DNS_STEPS, farm_n=FARM_N,
                 farm_steps=FARM_STEPS):
    import jax

    from repro import api

    chip = jax.devices()[:1]
    check_kernels(dns_n)
    print(f"  peak_bytes_in_use after the kernels: {peak_bytes(chip)[0]}")

    tpu = taylor_green(api.runtime(n=dns_n, nz=dns_n, backend="jnp"),
                       dns_steps)
    report_dns(f"taylor_green {dns_n}^3 x {dns_steps} steps (chip)", tpu)
    print(f"  peak_bytes_in_use after the DNS: {peak_bytes(chip)[0]}")
    with jax.default_device(jax.devices("cpu")[0]):
        cpu = taylor_green(api.runtime(n=dns_n, nz=dns_n, backend="jnp"),
                           dns_steps)
    report_dns("  same on the host CPU backend", cpu)
    same = drift(tpu["state"], cpu["state"], dns_steps, "chip vs CPU")
    print(f"  chip vs CPU bitwise: {same}")

    rt = api.runtime(n=farm_n, n_slots=FARM_SLOTS, backend="jnp")
    members = farm(rt, farm_steps)
    serial = rt.run("cavity", re=100.0, steps=farm_steps)
    for tag in ("re100-0", "re100-7"):
        same = drift(members[tag].state, serial.state, farm_steps,
                     f"farm {tag} vs serial run")
        print(f"  farm {tag} vs serial run bitwise: {same}")
    stats = api.compile_cache_stats()
    print(f"farm compile cache: {stats}")
    check(stats["misses"] == 1 and stats["entries"] == 1,
          f"expected one farm compile, got {stats}")

    print(f"peak_bytes_in_use after the farm: {peak_bytes(chip)[0]}")


def run_four_chips(dns_n=DNS_N, dns_steps=DNS_STEPS, farm_n=FARM_N,
                   farm_steps=FARM_STEPS):
    import jax

    from repro import api

    devices = jax.devices()
    check(len(devices) >= 4, f"--four-chips needs 4 devices, "
                             f"have {len(devices)}")
    devices = devices[:4]

    # the split runs first, so each chip's peak shows its own share
    split = taylor_green(api.runtime(
        n=dns_n, nz=dns_n, backend="jnp", mesh_shape=(4,),
        mesh_axes=("shard",), decomposition=((0, "shard"),)), dns_steps)
    report_dns(f"taylor_green {dns_n}^3 x over 4 chips", split)
    print(f"  peak_bytes_in_use per chip after the split DNS: "
          f"{peak_bytes(devices)}")
    rt22 = api.runtime(n=farm_n, n_slots=FARM_SLOTS, backend="jnp",
                       mesh_shape=(2, 2), mesh_axes=("slot", "shard"),
                       decomposition=((0, "shard"),))
    meshed = farm(rt22, farm_steps)
    print(f"  peak_bytes_in_use per chip after the (2, 2) farm: "
          f"{peak_bytes(devices)}")

    one = taylor_green(api.runtime(n=dns_n, nz=dns_n, backend="jnp"),
                       dns_steps)
    report_dns(f"taylor_green {dns_n}^3 on one chip", one)
    for k in ("err_vx", "energy", "div_max"):
        d = abs(split[k] - one[k])
        print(f"  split vs one chip {k}: |diff| {d:.3e} (bound {DIAG_TOL})")
        check(d < DIAG_TOL, f"split vs one chip: {k} differs by {d:.3e}")
    same = drift(split["state"], one["state"], dns_steps,
                 "split vs one chip")
    print(f"  split vs one chip bitwise: {same}")

    single = farm(api.runtime(n=farm_n, n_slots=FARM_SLOTS, backend="jnp"),
                  farm_steps)
    for tag, r in meshed.items():
        same = drift(r.state, single[tag].state, farm_steps,
                     f"(2, 2) farm vs one device {tag}")
        print(f"  (2, 2) farm vs one device {tag} bitwise: {same}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the paths that span four chips")
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "src"))
    try:
        from repro.launch.compile_cache import use_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repro package is not next to this script "
              f"({e})", file=sys.stderr)
        return 2
    cache_dir = use_compile_cache()

    import jax

    cache_events = collections.Counter()
    jax.monitoring.register_event_listener(
        lambda event, **_: cache_events.update(
            [event.rsplit("/", 1)[-1]])
        if event.startswith("/jax/compilation_cache/") else None)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (jax.devices()[0] is {dev.platform!r} "
              f"{dev.device_kind!r})", file=sys.stderr)
        return 1
    print(f"device: {dev.platform} {dev.device_kind} x {len(jax.devices())}; "
          f"jax {jax.__version__}; compile cache {cache_dir}")

    t0 = time.perf_counter()
    try:
        if args.four_chips:
            run_four_chips()
        else:
            run_one_chip()
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(f"persistent compile cache: hits "
          f"{cache_events['cache_hits']}, misses "
          f"{cache_events['cache_misses']}")
    print(f"wall_s: {time.perf_counter() - t0:.1f}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": 4 if args.four_chips else len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
