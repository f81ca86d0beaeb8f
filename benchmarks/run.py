"""Benchmark harness: one bench per paper table/figure + the roofline
deliverable — every result lands in the ``BENCH_*.json`` trajectory.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only cavity,...]
                                           [--smoke] [--out-dir DIR]

Each bench's result is written as ``BENCH_<name>.json`` in the fixed
``repro.bench.v1`` envelope (see :mod:`repro.obs.bench`): schema version,
bench name, creation time, host fingerprint, pass verdict, wall time, and
the bench's numbers under ``metrics``.  Every file is schema-validated
before it is written, so a malformed entry can never enter the
trajectory.

``--smoke`` runs a seconds-scale telemetry-enabled ensemble pass instead
of the full suite and emits ``BENCH_smoke.json`` — the CI fast lane runs
it on every push and archives the artifact, which is what keeps the
trajectory populated (and the schema honest) between real-hardware runs.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCHES = ["stencil", "cavity", "ensemble", "scaling", "roofline", "dist"]

# warm waves per mode in the smoke: the recorded steady numbers are
# best-of-N, damping CI scheduling noise (the 3% health gate binds on the
# deterministic cost model, not on these wall numbers)
WARM_WAVES = 3


def _wave(rt, reynolds: tuple, steps: int, tag: str, **kw):
    """One submit+drain wave; ``(sids, wall_s, all_finished)``."""
    t0 = time.perf_counter()
    sids = [rt.submit("cavity", re=re, steps=steps,
                      tag=f"{tag}-re{re:.0f}", **kw) for re in reynolds]
    out = rt.drain()
    wall = time.perf_counter() - t0
    ok = all(out[s].steps_done == steps and out[s].terminated == "steps"
             for s in sids)
    return sids, wall, ok


def run_smoke(out_dir: str) -> dict:
    """Telemetry-on mini ensemble: the first entry of any trajectory.

    Small enough for CI (seconds on one CPU), but it exercises the whole
    instrumented stack: front door -> farm -> ensemble step with timers,
    metrics, and per-sim traces — and its BENCH document carries the
    telemetry snapshot, so the artifact doubles as an observability
    regression record.

    Besides the baseline-compared ``steady_sim_steps_per_s`` (warm
    compile cache, health off, no steady checks), the smoke records the
    health-overhead pair: ``steady_sim_steps_per_s_checked`` (health off,
    sims carrying a steady tolerance, so the farm already syncs residuals
    at every ``check_steady_every`` boundary) vs
    ``steady_sim_steps_per_s_health`` (same duty cycle with the in-situ
    monitor compiled in, ring drains riding those same boundaries).
    Those wall numbers are informational; the number ``check_regression``
    holds to the 3% bound is ``health.model.modeled_overhead`` — the HLO
    cost model's price of one diagnostics pass amortized over the
    ``check_steady_every`` steps its chunk covers, lowered from the two
    farms' real compiled executables (see
    :func:`repro.obs.perf.health_overhead_model` for why wall-clock
    cannot gate at 3%).  The "zero extra host syncs" claim is gated
    separately and exactly: ``health.drains == health.boundaries``.
    """
    from repro import api, obs

    n, steps, slots = 12, 16, 2
    reynolds = (60.0, 140.0, 260.0, 380.0)
    # a tolerance no residual ever meets: the sims run their full step
    # budget, but the farm performs a real residual sync at every
    # check_steady_every boundary — the duty cycle health drains ride
    never_tol = 1e-30
    rt = api.runtime(n=n, n_slots=slots, jacobi_iters=8, telemetry=True,
                     check_every=8)
    sids, wall, cold_ok = _wave(rt, reynolds, steps, "cold")
    # warm waves on the now-warm compile cache: their throughput is the
    # stable number the regression gate compares (the cold wave's
    # includes the one-time ensemble-step compile)
    warm = [_wave(rt, reynolds, steps, f"warm{i}")
            for i in range(WARM_WAVES)]
    warm_wall = min(w for _, w, _ in warm)
    checked = [_wave(rt, reynolds, steps, f"checked{i}",
                     steady_tol=never_tol) for i in range(WARM_WAVES)]
    checked_wall = min(w for _, w, _ in checked)
    done = [cold_ok] + [ok for _, _, ok in warm + checked]
    traced = [rt.telemetry.trace.kinds_for(s) for s in sids]
    lifecycle_ok = all(
        ("submit" in k and "admit" in k and "result" in k) for k in traced)
    obs.validate_chrome_trace(rt.telemetry.trace.to_chrome())
    perf_doc = rt.perf_report().as_dict()

    # same farm shape and steady-check duty cycle, health monitor
    # compiled in: the ring drains ride the boundaries the checked waves
    # already sync at, so checked-vs-health isolates the monitor's cost
    rt_h = api.runtime(n=n, n_slots=slots, jacobi_iters=8, telemetry=True,
                       health=True, check_every=8)
    _, _, h_cold_ok = _wave(rt_h, reynolds, steps, "hcold",
                            steady_tol=never_tol)
    h_warm = [_wave(rt_h, reynolds, steps, f"hwarm{i}",
                    steady_tol=never_tol) for i in range(WARM_WAVES)]
    h_wall = min(w for _, w, _ in h_warm)
    done += [h_cold_ok] + [ok for _, _, ok in h_warm]
    svc_h = next(iter(rt_h._services.values()))
    boundaries = (svc_h.farm.device_steps
                  // svc_h.farm.check_steady_every)
    drains = int(rt_h.telemetry.metrics.get("health.drains") or 0)
    # the gated overhead number: deterministic HLO-cost price of the
    # monitor, from the two farms' real lowered executables
    svc = next(iter(rt._services.values()))
    model = obs.perf.health_overhead_model(
        svc.farm.exec, svc_h.farm.exec, svc_h.farm.check_steady_every)
    model_ok = (model["status"] == "ok"
                and model["modeled_overhead"] is not None
                and model["modeled_overhead"] <= 0.03)
    total_wall = wall + sum(w for _, w, _ in warm + checked) \
        + sum(w for _, w, _ in h_warm)

    doc = obs.make_bench_doc(
        "smoke",
        {
            "grid": f"{n}x{n}x4",
            "ensemble": len(reynolds),
            "slots": slots,
            "steps_per_sim": steps,
            "sim_steps_per_s": round(len(reynolds) * steps / wall, 1),
            "steady_sim_steps_per_s": round(
                len(reynolds) * steps / warm_wall, 1),
            "steady_sim_steps_per_s_checked": round(
                len(reynolds) * steps / checked_wall, 1),
            "steady_sim_steps_per_s_health": round(
                len(reynolds) * steps / h_wall, 1),
            "health": {"drains": drains, "boundaries": boundaries,
                       "model": model},
            "device_steps": rt.device_steps(),
            "compile_cache": api.compile_cache_stats(),
            "telemetry": rt.telemetry.snapshot(),
            "perf": perf_doc,
        },
        passed=all(done) and lifecycle_ok and drains == boundaries
        and model_ok,
        wall_s=round(total_wall, 3),
    )
    path = obs.write_bench(doc, out_dir)
    obs.load_bench(path)   # round-trip: the artifact on disk validates
    print(f"[benchmarks] smoke -> {path} "
          f"(passed={doc['passed']}, {doc['wall_s']}s)")
    print(rt.report())
    return doc


def run_health_smoke(out_dir: str) -> dict:
    """NaN-injection smoke: poison one slot of a health-monitored farm
    and verify the quarantine machinery end to end, leaving the health
    trace JSONL and the flight record in ``out_dir`` as CI artifacts.

    Checks (all must hold for ``passed``): the poisoned sim quarantines
    with ``terminated="diverged"``, every healthy sim finishes, the
    flight record reads back from disk, and the ring drained exactly
    once per harvest boundary (zero extra host syncs).
    """
    from repro import api, obs
    from repro.obs.health import load_flight_record

    n, slots, steps = 12, 4, 24
    trace_path = os.path.join(out_dir, "health_events.jsonl")
    flight_dir = os.path.join(out_dir, "flight-records")
    rt = api.runtime(n=n, n_slots=slots, check_every=8, jacobi_iters=8,
                     telemetry={"trace_path": trace_path},
                     health={"flight_dir": flight_dir})
    t0 = time.perf_counter()
    healthy = [rt.submit("cavity", re=re, steps=steps, tag=f"re{re:.0f}")
               for re in (80.0, 150.0, 240.0)]
    bad = rt.submit("cavity", re=100.0, steps=steps, dt=50.0, tag="poison")
    res = rt.drain()
    wall = time.perf_counter() - t0
    rt.telemetry.trace.close()   # flush the JSONL artifact

    quarantined = res[bad].terminated == "diverged"
    healthy_done = all(res[s].terminated == "steps"
                       and res[s].steps_done == steps for s in healthy)
    svc = next(iter(rt._services.values()))
    boundaries = svc.farm.device_steps // svc.farm.check_steady_every
    drains = int(rt.telemetry.metrics.get("health.drains") or 0)
    try:
        rec = load_flight_record(flight_dir, rt._routes[bad][1])
        flight_ok = rec["meta"]["tag"] == "poison" and len(rec["frames"])
    except Exception as e:
        print(f"[benchmarks] flight record unreadable: {e}")
        flight_ok = False

    doc = obs.make_bench_doc(
        "health_smoke",
        {
            "grid": f"{n}x{n}x4",
            "slots": slots,
            "quarantined": bool(quarantined),
            "quarantine_error": res[bad].error,
            "healthy_done": bool(healthy_done),
            "drains": drains,
            "boundaries": boundaries,
            "flight_record_ok": bool(flight_ok),
            "dashboard": rt.watch(),
        },
        passed=bool(quarantined and healthy_done and flight_ok
                    and drains == boundaries),
        wall_s=round(wall, 3),
    )
    path = obs.write_bench(doc, out_dir)
    obs.load_bench(path)
    print(f"[benchmarks] health_smoke -> {path} "
          f"(passed={doc['passed']}, {doc['wall_s']}s)")
    print(doc["metrics"]["dashboard"])
    return doc


_DURABILITY_CHILD = """\
import os, signal
from repro import api

rt = api.runtime(n={n}, n_slots=2, jacobi_iters=8,
                 store={{"path": {store!r}, "ttl_s": 1.0}})
sids = [rt.submit("cavity", re=re, steps={steps}, tag=tag)
        for re, tag in ((80.0, "a"), (160.0, "b"), (240.0, "c"))]
rt.enqueue("cavity", re=320.0, steps={steps}, tag="d")
svc = rt.services()[0]
svc.run(4)                     # a, b mid-flight; c queued; d detached
assert rt.evict(sids[0])       # a spills a durable resume pointer
svc.run(2)                     # b keeps going; c admitted into a's slot
print("READY", flush=True)
os.kill(os.getpid(), signal.SIGKILL)
"""


def run_durability_smoke(out_dir: str) -> dict:
    """Kill-and-resume smoke for the durable job engine (repro.jobs).

    A child process submits four simulations against a shared SQLite
    ``JobStore`` (one evicted with a durable snapshot, two mid-run, one
    detached enqueue) and SIGKILLs itself mid-chunk.  After the dead
    process's leases expire, a fresh Runtime on the same store must (a)
    resume every incomplete job BEFORE claiming queued work, (b) finish
    all four, (c) execute each job exactly once (one terminal ``result``
    audit event per job), and (d) produce final states bitwise-identical
    to an uninterrupted run of the same requests.  The store file and its
    snapshot directories are left in ``out_dir`` as CI artifacts.
    """
    import shutil
    import signal as _signal
    import subprocess

    import numpy as np

    from repro import api, obs, jobs
    from repro.jobs import JobStore

    n, steps = 12, 12
    store_dir = os.path.join(out_dir, "durability-store")
    shutil.rmtree(store_dir, ignore_errors=True)
    store_path = os.path.join(store_dir, "jobs.sqlite")
    t0 = time.perf_counter()

    env = dict(os.environ)
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    # the child runs on the CPU: a chip this process holds is not shared
    env["JAX_PLATFORMS"] = "cpu"
    child = subprocess.run(
        [sys.executable, "-c",
         _DURABILITY_CHILD.format(n=n, steps=steps, store=store_path)],
        env=env, capture_output=True, text=True, timeout=600)
    killed = ("READY" in child.stdout
              and child.returncode == -_signal.SIGKILL)
    if not killed:
        print(f"[benchmarks] durability child failed:\n{child.stderr}")

    probe = JobStore(store_path)
    tags = {j.tag: j.job_id for j in probe.jobs()}
    incomplete = {j.job_id for j in probe.jobs()
                  if j.status in jobs.INCOMPLETE}
    seq0 = probe.last_seq()
    orphaned_ok = (len(tags) == 4 and len(incomplete) >= 2
                   and probe.latest_snapshot(tags.get("a", -1)) is not None)
    time.sleep(1.2)                      # let the dead leases expire

    rt = api.runtime(n=n, n_slots=2, jacobi_iters=8, telemetry=True,
                     store={"path": store_path, "ttl_s": 30.0})
    resumed = len(rt._jobs_local & incomplete)
    rt.drain()
    st = rt.store
    all_done = st.counts()[jobs.DONE] == 4 and st.queue_depth() == 0
    # resume-first, from the audit log: every claim of an incomplete job
    # precedes every claim of a queued one
    claims = {e["job_id"]: e["seq"] for e in st.events(after_seq=seq0)
              if e["event"] in ("claim", "takeover")
              and e["owner"] == st.owner}
    queued_seqs = [s for j, s in claims.items() if j not in incomplete]
    resumed_first = bool(incomplete) and bool(queued_seqs) and \
        max(claims[j] for j in incomplete) < min(queued_seqs)
    single_execution = all(
        len(st.events(jid, event="result")) == 1 for jid in tags.values())

    # bitwise parity against a never-interrupted run of the same requests
    ref = api.runtime(n=n, n_slots=2, jacobi_iters=8)
    ref_sids = {tag: ref.submit("cavity", re=re, steps=steps, tag=tag)
                for re, tag in ((80.0, "a"), (160.0, "b"),
                                (240.0, "c"), (320.0, "d"))}
    ref_res = ref.drain()
    parity_ok = bool(tags) and all(
        np.array_equal(st.load_result(jid)[f],
                       np.asarray(ref_res[ref_sids[tag]].state[f]))
        for tag, jid in tags.items()
        for f in ("vx", "vy", "vz", "p")) if all_done else False

    wall = time.perf_counter() - t0
    doc = obs.make_bench_doc(
        "durability_smoke",
        {
            "grid": f"{n}x{n}x4",
            "jobs": len(tags),
            "killed": bool(killed),
            "orphaned_ok": bool(orphaned_ok),
            "incomplete_at_restart": len(incomplete),
            "resumed": resumed,
            "resumed_first": bool(resumed_first),
            "lease_takeovers": st.takeovers,
            "single_execution": bool(single_execution),
            "all_done": bool(all_done),
            "parity_ok": bool(parity_ok),
            "store_counts": st.counts(),
        },
        passed=bool(killed and orphaned_ok and all_done and resumed >= 1
                    and resumed_first and single_execution and parity_ok),
        wall_s=round(wall, 3),
    )
    path = obs.write_bench(doc, out_dir)
    obs.load_bench(path)
    print(f"[benchmarks] durability_smoke -> {path} "
          f"(passed={doc['passed']}, {doc['wall_s']}s)")
    return doc


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None)
    ap.add_argument("--smoke", action="store_true",
                    help="seconds-scale telemetry bench -> BENCH_smoke.json")
    ap.add_argument("--health-smoke", action="store_true",
                    help="NaN-injection quarantine smoke -> "
                         "BENCH_health_smoke.json + health_events.jsonl + "
                         "flight-records/")
    ap.add_argument("--durability-smoke", action="store_true",
                    help="SIGKILL-and-resume durable-jobs smoke -> "
                         "BENCH_durability_smoke.json + durability-store/")
    ap.add_argument("--out-dir", default=".",
                    help="where BENCH_*.json artifacts land")
    args = ap.parse_args()

    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    if args.smoke or args.health_smoke or args.durability_smoke:
        ok = True
        if args.smoke:
            ok &= run_smoke(args.out_dir)["passed"]
        if args.health_smoke:
            ok &= run_health_smoke(args.out_dir)["passed"]
        if args.durability_smoke:
            ok &= run_durability_smoke(args.out_dir)["passed"]
        sys.exit(0 if ok else 1)

    from repro import obs

    names = args.only.split(",") if args.only else BENCHES
    results = []
    for name in names:
        mod = __import__(f"benchmarks.bench_{name}", fromlist=["run"])
        t0 = time.time()
        print(f"=== bench_{name} ===", flush=True)
        try:
            res = mod.run(quick=args.quick)
            res["wall_s"] = res.get("wall_s", round(time.time() - t0, 1))
        except Exception as e:  # pragma: no cover
            res = {"bench": name, "passed": False,
                   "error": f"{type(e).__name__}: {e}",
                   "wall_s": round(time.time() - t0, 1)}
        print(json.dumps(res, indent=1, default=str), flush=True)
        doc = obs.make_bench_doc(
            name, {k: v for k, v in res.items()
                   if k not in ("passed", "wall_s")},
            passed=bool(res.get("passed")), wall_s=res["wall_s"])
        path = obs.write_bench(doc, args.out_dir)
        print(f"[benchmarks] wrote {path}", flush=True)
        results.append(res)

    n_pass = sum(1 for r in results if r.get("passed"))
    print(f"\n[benchmarks] {n_pass}/{len(results)} passed")
    if n_pass < len(results):
        for r in results:
            if not r.get("passed"):
                print(f"  FAILED: {r['bench']}: {r.get('error', '')}")
        sys.exit(1)


if __name__ == "__main__":
    main()
