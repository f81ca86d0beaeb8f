"""Bench regression gate: fresh ``BENCH_smoke.json`` vs committed baseline.

    PYTHONPATH=src python -m benchmarks.check_regression \
        --fresh bench-artifacts/BENCH_smoke.json \
        [--baseline benchmarks/baselines/BENCH_smoke.json] \
        [--max-regression 0.2] [--write-report report.md]

Failure conditions (exit 1, CI-red):

* the fresh bench itself did not pass;
* steady throughput (``steady_sim_steps_per_s``, warm compile cache)
  regressed by more than ``--max-regression`` (default 20%) against the
  baseline — only when fresh and baseline ran on comparable hosts (same
  backend + device count); cross-host wall-clock compares are skipped
  with a warning, never silently trusted;
* a perf row's achieved utilization collapsed to under half its baseline
  (same-host only);
* any fresh perf row reports a halo-byte MISMATCH or turned
  ``unparsed`` relative to its baseline row;
* a ``BENCH_ensemble_pallas.json`` artifact breaks a structural
  invariant — farm-vs-serial parity to float32 ulps, one compiled
  executable per static signature, a throughput row per ensemble size —
  gated baseline-free on any host (``structural_failures``).

When the throughput gate trips, the perf attribution explains *why* by
diffing the predicted-cost rows: measured seconds up with predicted
FLOPs/bytes/wire flat means a runtime/scheduling regression (not added
work); collective seconds or wire bytes up with halo analytics flat
means a schedule/decomposition regression; HBM bytes up means the
compiled program itself grew.  A missing baseline warns and passes
(bootstrap) — commit one with ``benchmarks/bless_baseline.py``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

DEFAULT_BASELINE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "baselines", "BENCH_smoke.json")
THROUGHPUT_KEYS = ("steady_sim_steps_per_s", "sim_steps_per_s")
UTIL_COLLAPSE = 0.5          # fresh utilization < 50% of baseline -> fail
# the health monitor's modeled steady-state cost (one diagnostics pass
# amortized over the check_steady_every steps its chunk covers, priced
# by the HLO cost model on the real lowered executables) must stay
# within 3% of the health-off step cost.  Deterministic, so it gates
# baseline-free on any host — unlike a wall-clock ratio of two
# separately compiled programs, which carries several-percent
# process-level layout variance and would make a 3% gate a coin flip.
HEALTH_OVERHEAD = 0.03


def _throughput(doc: dict) -> tuple[float | None, str | None]:
    for k in THROUGHPUT_KEYS:
        v = doc.get("metrics", {}).get(k)
        if v:
            return float(v), k
    return None, None


def _perf_rows(doc: dict) -> dict:
    rows = doc.get("metrics", {}).get("perf", {}).get("rows", [])
    return {r.get("name"): r for r in rows if isinstance(r, dict)}


def _same_host(fresh: dict, baseline: dict) -> bool:
    fh, bh = fresh.get("host", {}), baseline.get("host", {})
    return (fh.get("backend") == bh.get("backend")
            and fh.get("device_count") == bh.get("device_count"))


def _ratio(a, b):
    if not a or not b:
        return None
    return float(a) / float(b)


def explain(base_row: dict, fresh_row: dict) -> list[str]:
    """Attribute a slowdown by diffing one perf row against its baseline."""
    name = fresh_row.get("name", "?")
    notes = []
    rm = _ratio(fresh_row.get("measured_s"), base_row.get("measured_s"))
    rh = _ratio(fresh_row.get("hbm_bytes"), base_row.get("hbm_bytes"))
    rw = _ratio(fresh_row.get("collective_wire_bytes"),
                base_row.get("collective_wire_bytes"))
    rc = _ratio(fresh_row.get("collective_s"), base_row.get("collective_s"))
    halo_flat = (fresh_row.get("halo_bytes_analytic")
                 == base_row.get("halo_bytes_analytic"))
    if rm and rm > 1.2:
        notes.append(f"{name}: measured_s grew {rm:.2f}x")
        if rh and rh > 1.2:
            notes.append(f"{name}: predicted HBM bytes grew {rh:.2f}x -> "
                         "the compiled program itself does more memory "
                         "traffic (solver/fusion change)")
        if rc and rc > 1.5 or (rw and rw > 1.5):
            if halo_flat:
                notes.append(
                    f"{name}: collective_s grew "
                    f"{(rc or rw):.2f}x, analytic halo bytes unchanged -> "
                    "schedule regression (extra/badly-placed collectives), "
                    "not a decomposition change")
            else:
                notes.append(f"{name}: collective traffic AND analytic "
                             "halo bytes changed -> decomposition change")
        if (rh is None or rh <= 1.2) and (rw is None or rw <= 1.2):
            notes.append(f"{name}: predicted cost flat while measured time "
                         "grew -> runtime/dispatch regression, not added "
                         "work")
    return notes


def structural_failures(fresh: dict) -> list[str]:
    """Host-independent invariants, gated without any baseline, on any
    machine.

    ``ensemble_pallas``: the farm really ran the Pallas template, matched
    serial runs to float32 ulps, and compiled exactly one executable per
    static signature.  ``smoke``: the health monitor's modeled steady-state
    cost within ``HEALTH_OVERHEAD`` of the health-off step, and ring
    drains exactly on the harvest cadence.  ``health_smoke``: the
    NaN-injection quarantine
    actually quarantined, kept the healthy slots, and left a readable
    flight record.  ``durability_smoke``: a SIGKILLed farm really
    resumed from the job store — incomplete jobs first, exactly once,
    bitwise identical to an uninterrupted run.
    """
    if fresh.get("bench") == "smoke":
        return _smoke_health_failures(fresh)
    if fresh.get("bench") == "health_smoke":
        return _health_smoke_failures(fresh)
    if fresh.get("bench") == "durability_smoke":
        return _durability_smoke_failures(fresh)
    if fresh.get("bench") != "ensemble_pallas":
        return []
    m = fresh.get("metrics", {})
    fails = []
    if not str(m.get("resolved_backend", "")).startswith("pallas"):
        fails.append("ensemble_pallas: resolved_backend "
                     f"{m.get('resolved_backend')!r} is not a pallas "
                     "backend")
    rows = m.get("batches") or []
    if not rows:
        fails.append("ensemble_pallas: no per-ensemble throughput rows")
    for r in rows:
        if not (isinstance(r, dict) and r.get("farm_steps_per_s", 0) > 0):
            fails.append(f"ensemble_pallas: ensemble={r.get('ensemble')} "
                         "row has no farm throughput")
    if m.get("parity", {}).get("ok") is not True:
        fails.append("ensemble_pallas: farm-vs-serial parity did not hold "
                     "(scalar-table regression?)")
    misses = m.get("compile_cache", {}).get("misses")
    if misses != m.get("expected_compile_misses"):
        fails.append(
            f"ensemble_pallas: {misses} compile misses, expected "
            f"{m.get('expected_compile_misses')} — not one executable per "
            "static signature (per-scalar recompile regression?)")
    return fails


def _smoke_health_failures(fresh: dict) -> list[str]:
    """Health-overhead gate inside one smoke artifact, baseline-free.

    Two deterministic invariants: the modeled steady-state cost of the
    monitor (``health.model.modeled_overhead`` — one diagnostics pass
    amortized over its chunk, priced by the HLO cost model on both
    farms' real lowered executables) within ``HEALTH_OVERHEAD``, and
    ring drains landing exactly on the harvest cadence (zero extra host
    syncs).  The wall-clock pair ``steady_sim_steps_per_s_checked`` /
    ``_health`` stays recorded in the artifact for humans but is not
    gated — see :func:`repro.obs.perf.health_overhead_model`.  Older
    artifacts without a health block pass untouched (bootstrap); an
    artifact that records health throughput but no model fails, so the
    model cannot be dropped silently."""
    m = fresh.get("metrics", {})
    fails = []
    if "health" not in m:
        return fails
    h = m.get("health", {})
    model = h.get("model")
    if not model:
        if m.get("steady_sim_steps_per_s_health"):
            fails.append("smoke: health throughput recorded but no "
                         "health.model block — the cost-model gate was "
                         "dropped")
        return fails
    if model.get("status") != "ok":
        fails.append(f"smoke: health cost model unparsed "
                     f"({model.get('error')}) — overhead cannot be gated")
    elif model.get("modeled_overhead", 1.0) > HEALTH_OVERHEAD:
        fails.append(
            f"smoke: modeled health overhead "
            f"{100 * model['modeled_overhead']:.2f}% exceeds the "
            f"{100 * HEALTH_OVERHEAD:.0f}% bound — the diagnostics pass "
            f"moves {model.get('hbm_bytes_diag_per_chunk'):.3g} HBM "
            f"bytes per chunk against a "
            f"{model.get('hbm_bytes_step'):.3g}-byte step (heavier "
            "diagnostics, or a shorter check_steady_every cadence?)")
    if h.get("drains") != h.get("boundaries"):
        fails.append(
            f"smoke: {h.get('drains')} health drains over "
            f"{h.get('boundaries')} harvest boundaries — the ring is "
            "not draining exactly on the check_steady_every cadence")
    return fails


def _health_smoke_failures(fresh: dict) -> list[str]:
    m = fresh.get("metrics", {})
    fails = []
    if m.get("quarantined") is not True:
        fails.append("health_smoke: the poisoned sim was not quarantined "
                     "(no terminated='diverged' result)")
    if m.get("healthy_done") is not True:
        fails.append("health_smoke: a healthy sim did not finish — "
                     "quarantine leaked into other slots")
    if m.get("flight_record_ok") is not True:
        fails.append("health_smoke: flight record missing or unreadable")
    if m.get("drains") != m.get("boundaries"):
        fails.append(
            f"health_smoke: {m.get('drains')} drains over "
            f"{m.get('boundaries')} boundaries — extra host syncs")
    return fails


def _durability_smoke_failures(fresh: dict) -> list[str]:
    """Kill-and-resume invariants, all host-independent.

    The child process must really have died by SIGKILL mid-run leaving
    orphaned rows behind; the restarted Runtime must resume every
    incomplete job *before* claiming fresh queued work, execute each
    job exactly once (one ``result`` audit event per row), drain the
    queue to empty, and produce results bitwise identical to an
    uninterrupted run."""
    m = fresh.get("metrics", {})
    fails = []
    if m.get("killed") is not True:
        fails.append("durability_smoke: child was not SIGKILLed mid-run — "
                     "the smoke never exercised a crash")
    if m.get("orphaned_ok") is not True:
        fails.append("durability_smoke: expected orphaned store state "
                     "(incomplete rows + evict snapshot) not found after "
                     "the kill")
    if not m.get("resumed", 0) >= 1:
        fails.append("durability_smoke: restarted Runtime resumed no "
                     "incomplete jobs")
    if m.get("resumed_first") is not True:
        fails.append("durability_smoke: a queued job was claimed before "
                     "the orphaned incomplete jobs — resume-first order "
                     "violated")
    if m.get("single_execution") is not True:
        fails.append("durability_smoke: a job recorded more than one "
                     "terminal 'result' event — double execution")
    if m.get("all_done") is not True:
        fails.append("durability_smoke: queue did not drain to all-done "
                     f"(store_counts={m.get('store_counts')})")
    if m.get("parity_ok") is not True:
        fails.append("durability_smoke: resumed results are not bitwise "
                     "identical to an uninterrupted run")
    return fails


def compare(fresh: dict, baseline: dict | None,
            max_regression: float = 0.2) -> dict:
    """Pure gate logic over two ``repro.bench.v1`` docs (the unit-tested
    core of the CLI)."""
    failures: list[str] = []
    warnings: list[str] = []
    explanations: list[str] = []

    if not fresh.get("passed"):
        failures.append("fresh bench did not pass")
    failures.extend(structural_failures(fresh))
    if baseline is not None and baseline.get("bench") != fresh.get("bench"):
        warnings.append(
            f"baseline is for bench {baseline.get('bench')!r}, fresh is "
            f"{fresh.get('bench')!r}: baseline gates skipped")
        baseline = None
    fresh_perf = _perf_rows(fresh)
    for name, row in fresh_perf.items():
        if row.get("halo_match") is False:
            failures.append(
                f"perf row {name}: predicted halo bytes "
                f"{row.get('halo_bytes_predicted')} != analytic "
                f"{row.get('halo_bytes_analytic')}")

    if baseline is None:
        warnings.append("no baseline: throughput/utilization gates skipped "
                        "(bless one with benchmarks/bless_baseline.py)")
        return {"passed": not failures, "failures": failures,
                "warnings": warnings, "explanations": explanations}

    base_perf = _perf_rows(baseline)
    for name, row in fresh_perf.items():
        b = base_perf.get(name)
        if b and b.get("status") == "ok" and row.get("status") != "ok":
            failures.append(f"perf row {name} turned "
                            f"{row.get('status')!r} (was ok): "
                            f"{row.get('error')}")

    if not _same_host(fresh, baseline):
        warnings.append(
            f"host mismatch (fresh {fresh.get('host')}, baseline "
            f"{baseline.get('host')}): wall-clock gates skipped")
        return {"passed": not failures, "failures": failures,
                "warnings": warnings, "explanations": explanations}

    ft, fk = _throughput(fresh)
    bt, bk = _throughput(baseline)
    if ft is None or bt is None:
        warnings.append("throughput metric missing from fresh or baseline")
    elif ft < bt * (1.0 - max_regression):
        failures.append(
            f"throughput regression: {fk}={ft:g} vs baseline {bk}={bt:g} "
            f"({100 * (1 - ft / bt):.1f}% slower, gate "
            f"{100 * max_regression:.0f}%)")
        for name, row in fresh_perf.items():
            if name in base_perf:
                explanations.extend(explain(base_perf[name], row))

    for name, row in fresh_perf.items():
        b = base_perf.get(name)
        if not b:
            continue
        fu, bu = row.get("utilization"), b.get("utilization")
        if fu is not None and bu and fu < UTIL_COLLAPSE * bu:
            failures.append(
                f"utilization collapse on {name}: {fu:.3g} vs baseline "
                f"{bu:.3g} (gate {UTIL_COLLAPSE:.0%} of baseline)")
            explanations.extend(explain(b, row))

    return {"passed": not failures, "failures": failures,
            "warnings": warnings, "explanations": explanations}


def render(verdict: dict) -> str:
    lines = ["# bench regression gate",
             f"**{'PASS' if verdict['passed'] else 'FAIL'}**", ""]
    for w in verdict["warnings"]:
        lines.append(f"- warning: {w}")
    for f in verdict["failures"]:
        lines.append(f"- FAIL: {f}")
    if verdict["explanations"]:
        lines.append("")
        lines.append("## attribution")
        for e in verdict["explanations"]:
            lines.append(f"- {e}")
    return "\n".join(lines) + "\n"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fresh", required=True,
                    help="freshly produced BENCH_smoke.json")
    ap.add_argument("--baseline", default=DEFAULT_BASELINE)
    ap.add_argument("--max-regression", type=float, default=0.2,
                    help="tolerated fractional throughput drop (0.2 = 20%%)")
    ap.add_argument("--write-report", default=None,
                    help="also write the verdict as markdown here")
    args = ap.parse_args(argv)

    from repro import obs

    fresh = obs.load_bench(args.fresh)
    baseline = None
    if os.path.exists(args.baseline):
        baseline = obs.load_bench(args.baseline)
    verdict = compare(fresh, baseline, max_regression=args.max_regression)
    text = render(verdict)
    print(text)
    if args.write_report:
        with open(args.write_report, "w") as f:
            f.write(text)
        with open(args.write_report + ".json", "w") as f:
            json.dump(verdict, f, indent=1)
    return 0 if verdict["passed"] else 1


if __name__ == "__main__":
    sys.exit(main())
