"""repro.obs.perf: cost-model accounting end to end — the analytic
ghost-zone model pinned against the HLO-predicted collective-permute
bytes (the fast-lane AbstractMesh lowering needs no devices), the
perf-on/off bitwise contract, the unparsed-HLO fallback, the chip
registry, the Prometheus surface, and the bench regression gate
(including the injected-2x-slowdown failure)."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from benchmarks.check_regression import compare
from repro import api, obs
from repro.cfd.ns3d import CFDConfig
from repro.core.rooflinemodel import (
    CHIPS, V5E, Chip, chip_for_device, resolve_chip,
)
from repro.launch import hlo_cost
from repro.obs import perf
from repro.sim import SimulationService

N = 12
KW = dict(jacobi_iters=8)


def _cfg(n=16, **kw):
    kw.setdefault("jacobi_iters", 8)
    return CFDConfig(shape=(n, n, n), extent=1.0, case="cavity",
                     decomposition={0: "shard"}, **kw)


# ---------------------------------------------------------------------------
# predicted halo bytes == analytic ghost-zone bytes (the tentpole check)
# ---------------------------------------------------------------------------
class TestHaloPrediction:
    def test_decomposed_step_permute_bytes_match_analytic(self):
        """The slots × shards cavity step's collective-permutes, counted
        by the trip-count-aware cost model over the AbstractMesh
        lowering, must carry exactly the bytes the decomposition plan
        implies — velocity halos, divergence/projection one-sided pads,
        and the Jacobi loop multiplied by its trip count."""
        cfg = _cfg(16)
        text, active = perf.decomposed_step_hlo(
            cfg, n_slots=4, mesh_axes=(("slot", 2), ("shard", 2)))
        assert active == {0: "shard"}
        cost, status, err = hlo_cost.safe_analyze(text, 4)
        assert status == "ok" and err is None
        predicted = cost.collective_bytes["collective-permute"]
        analytic = perf.halo_bytes_per_step(
            cfg, active, {"slot": 2, "shard": 2},
            slots_local=perf._slots_local(4, 2))
        assert predicted == analytic
        # permute inventory on one decomposed axis — velocity two-sided
        # (2×3), divergence one-sided (3), jacobi two-sided × trip count
        # (2×iters), projection one-sided (1)
        assert cost.collective_counts["collective-permute"] == \
            2 * 3 + 3 + 2 * cfg.jacobi_iters + 1
        # the pressure solve's global mean is an all-reduce, not a permute
        assert cost.collective_counts["all-reduce"] >= 1

    def test_fused_sweeps_widen_the_analytic_halo(self):
        """The communication-avoiding smoother (fused_sweeps=k) trades
        k-wide halos for k-fewer exchanges; both sides of the bookkeeping
        must move together."""
        cfg = _cfg(16, fused_sweeps=2)
        text, active = perf.decomposed_step_hlo(
            cfg, n_slots=2, mesh_axes=(("slot", 1), ("shard", 2)))
        cost, status, _ = hlo_cost.safe_analyze(text, 2)
        assert status == "ok"
        analytic = perf.halo_bytes_per_step(
            cfg, active, {"slot": 1, "shard": 2},
            slots_local=perf._slots_local(2, 1))
        assert cost.collective_bytes["collective-permute"] == analytic

    def test_runtime_report_carries_the_match(self):
        rt = api.runtime(n=N, n_slots=2, telemetry=True, **KW)
        rt.submit("cavity", re=100.0, steps=4)
        rt.drain()
        rep = rt.perf_report()
        rows = rep.rows()
        assert len(rows) == 1 and rows[0]["kind"] == "farm-step"
        assert rows[0]["status"] == "ok"
        assert rows[0]["measured_s"] and rows[0]["measured_s"] > 0
        assert rows[0]["bottleneck"] in ("compute", "memory", "collective")
        text = rt.report(perf=True)
        assert "perf accounting" in text and "farm/cavity" in text


# ---------------------------------------------------------------------------
# perf accounting is observation-only: outputs bitwise identical on/off
# ---------------------------------------------------------------------------
class TestBitwiseInvisible:
    @settings(max_examples=3, deadline=None)
    @given(re=st.sampled_from([80.0, 160.0, 320.0]),
           steps=st.integers(min_value=3, max_value=8))
    def test_perf_accounting_never_perturbs_results(self, re, steps):
        def run(with_perf):
            rt = api.runtime(n=N, n_slots=2,
                             telemetry=bool(with_perf), **KW)
            sid = rt.submit("cavity", re=re, steps=steps)
            rt.drain()
            if with_perf:
                rt.report(perf=True)         # lowers + costs mid-session
                sid2 = rt.submit("cavity", re=re, steps=steps)
                rt.drain()
                a, b = rt.result(sid), rt.result(sid2)
                for f in ("vx", "vy", "vz", "p"):
                    np.testing.assert_array_equal(a.state[f], b.state[f])
            return rt.result(sid)

        on, off = run(True), run(False)
        assert on.steps_done == off.steps_done
        for f in ("vx", "vy", "vz", "p"):
            np.testing.assert_array_equal(on.state[f], off.state[f])


# ---------------------------------------------------------------------------
# unparsed fallback: never raise into a drive loop
# ---------------------------------------------------------------------------
class TestUnparsedFallback:
    def test_safe_analyze_flags_garbage(self):
        cost, status, err = hlo_cost.safe_analyze("not hlo at all", 1)
        assert status == "unparsed" and err
        assert cost.flops == 0.0 and cost.bytes == 0.0

    def test_cost_row_and_report_survive_garbage(self):
        row = perf.cost_row_from_hlo("HloModule m {", name="x", kind="farm-step")
        assert row.status == "unparsed"
        rep = perf.PerfReport([row], chip="cpu-host")
        d = rep.rows()[0]
        assert d["bottleneck"] == "unknown" and d["utilization"] is None
        assert "unparsed" in rep.render()
        perf.validate_perf(rep.as_dict())     # still schema-complete

    def test_validate_perf_names_problems(self):
        with pytest.raises(ValueError, match="schema"):
            perf.validate_perf({"schema": "nope", "chip": {"name": "x"},
                                "rows": []})
        with pytest.raises(ValueError, match="rows"):
            perf.validate_perf({"schema": perf.PERF_SCHEMA,
                                "chip": {"name": "x"}, "rows": None})


# ---------------------------------------------------------------------------
# chip registry (the hardcoded-v5e bugfix)
# ---------------------------------------------------------------------------
class TestChipRegistry:
    def test_auto_resolves_to_the_running_platform(self):
        import jax

        dev = jax.devices()[0]
        chip = resolve_chip("auto")
        assert chip is chip_for_device(dev.platform, dev.device_kind)
        assert resolve_chip(None) is chip

    @pytest.mark.parametrize("platform,kind,name", [
        ("tpu", "TPU v5 lite", "tpu-v5e"),
        ("cpu", "cpu", "cpu-host"),
    ])
    def test_devices_resolve_by_kind(self, platform, kind, name):
        assert chip_for_device(platform, kind) is CHIPS[name]

    @pytest.mark.parametrize("platform,kind", [
        ("tpu", "TPU v4"),          # a TPU generation with no peaks here
        ("tpu", "TPU v5"),          # v5p must not borrow v5e's numbers
        ("gpu", "NVIDIA A100-SXM4-40GB"),
    ])
    def test_unknown_devices_raise(self, platform, kind):
        with pytest.raises(KeyError, match="no peaks"):
            chip_for_device(platform, kind)

    def test_names_and_passthrough(self):
        assert resolve_chip("tpu-v5e") is V5E
        mine = Chip(name="custom")
        assert resolve_chip(mine) is mine
        with pytest.raises(KeyError, match="unknown chip"):
            resolve_chip("tpu-v9000")

    def test_report_attributes_against_the_resolved_chip(self):
        row = perf.CostRow(name="r", kind="farm-step", flops=1e9,
                           hbm_bytes=1e6, measured_s=1e-3, invocations=1)
        cpu = perf.PerfReport([row], chip="cpu-host").rows()[0]
        tpu = perf.PerfReport([row], chip="tpu-v5e").rows()[0]
        assert cpu["compute_s"] > tpu["compute_s"]   # smaller peak, more s
        assert cpu["utilization"] > tpu["utilization"]


# ---------------------------------------------------------------------------
# Prometheus surface
# ---------------------------------------------------------------------------
class TestPrometheus:
    def test_registry_text_format(self):
        reg = obs.Registry()
        reg.inc("farm.steps", 3, farm="a/b")
        reg.set("farm.occupancy", 0.5)
        reg.observe("service.latency_seconds", 0.004)
        text = reg.to_prometheus()
        assert "# TYPE repro_farm_steps counter" in text
        assert 'repro_farm_steps{farm="a/b"} 3' in text
        assert "# TYPE repro_farm_occupancy gauge" in text
        assert "# TYPE repro_service_latency_seconds histogram" in text
        assert 'repro_service_latency_seconds_bucket{le="+Inf"} 1' in text
        assert "repro_service_latency_seconds_count 1" in text

    def test_service_scrape_includes_perf_gauges(self):
        svc = SimulationService(
            CFDConfig(shape=(N, N, N), extent=1.0, case="cavity", **KW),
            n_slots=2, telemetry=obs.telemetry())
        from repro.sim.farm import SimRequest

        svc.submit(SimRequest(sid=0, config=svc.farm.base_config,
                              steps=3))
        svc.drain()
        text = svc.prometheus_text(perf=True)
        assert "repro_perf_utilization" in text
        assert "repro_perf_bottleneck" in text
        assert "repro_farm_" in text      # farm metrics ride along

    def test_disabled_telemetry_scrapes_empty(self):
        svc = SimulationService(
            CFDConfig(shape=(N, N, N), extent=1.0, case="cavity", **KW),
            n_slots=2)
        assert svc.prometheus_text() == ""


# ---------------------------------------------------------------------------
# the regression gate
# ---------------------------------------------------------------------------
def _bench_doc(tp=100.0, *, passed=True, util=0.2, measured=1e-3,
               wire=6656.0, halo_match=True, host=None, status="ok"):
    row = {k: 0 for k in perf.ROW_KEYS}
    row.update(name="farm/cavity/sig000", kind="farm-step", status=status,
               measured_s=measured, utilization=util,
               collective_wire_bytes=wire, collective_s=wire / 5e10,
               halo_bytes_analytic=6656.0,
               halo_bytes_predicted=6656.0 if halo_match else 9999.0,
               halo_match=halo_match, hbm_bytes=1e6, flops=0.0)
    return {
        "schema": obs.BENCH_SCHEMA, "bench": "smoke", "passed": passed,
        "host": host or {"backend": "cpu", "device_count": 1},
        "metrics": {
            "steady_sim_steps_per_s": tp,
            "perf": {"schema": perf.PERF_SCHEMA,
                     "chip": {"name": "cpu-host"}, "dtype": "f32",
                     "rows": [row]},
        },
    }


class TestRegressionGate:
    def test_identical_docs_pass(self):
        v = compare(_bench_doc(), _bench_doc())
        assert v["passed"] and not v["failures"]

    def test_injected_2x_slowdown_fails_with_attribution(self):
        """The acceptance scenario: halve throughput, double measured
        seconds, leave the predicted cost untouched — the gate must fail
        AND blame the runtime rather than the program."""
        fresh = _bench_doc(tp=50.0, measured=2e-3, util=0.1)
        v = compare(fresh, _bench_doc(tp=100.0))
        assert not v["passed"]
        assert any("throughput regression" in f for f in v["failures"])
        assert any("50.0% slower" in f for f in v["failures"])
        assert any("predicted cost flat" in e for e in v["explanations"])

    def test_within_gate_passes(self):
        v = compare(_bench_doc(tp=85.0), _bench_doc(tp=100.0))
        assert v["passed"]

    def test_utilization_collapse_fails(self):
        v = compare(_bench_doc(util=0.01), _bench_doc(util=0.2))
        assert not v["passed"]
        assert any("utilization collapse" in f for f in v["failures"])

    def test_collective_growth_blames_the_schedule(self):
        fresh = _bench_doc(tp=40.0, measured=3e-3, wire=3 * 6656.0)
        v = compare(fresh, _bench_doc(tp=100.0))
        assert not v["passed"]
        assert any("schedule regression" in e for e in v["explanations"])

    def test_host_mismatch_skips_wall_clock_gates(self):
        fresh = _bench_doc(tp=10.0, host={"backend": "cpu",
                                          "device_count": 8})
        v = compare(fresh, _bench_doc(tp=100.0))
        assert v["passed"]
        assert any("host mismatch" in w for w in v["warnings"])

    def test_halo_mismatch_fails_even_cross_host(self):
        fresh = _bench_doc(halo_match=False,
                           host={"backend": "tpu", "device_count": 4})
        v = compare(fresh, _bench_doc())
        assert not v["passed"]
        assert any("halo bytes" in f for f in v["failures"])

    def test_missing_baseline_warns_and_passes(self):
        v = compare(_bench_doc(), None)
        assert v["passed"]
        assert any("no baseline" in w for w in v["warnings"])

    def test_row_turned_unparsed_fails(self):
        v = compare(_bench_doc(status="unparsed"), _bench_doc())
        assert not v["passed"]
        assert any("turned 'unparsed'" in f for f in v["failures"])

    def test_baseline_for_other_bench_is_ignored(self):
        fresh = dict(_bench_doc(tp=10.0), bench="ensemble")
        v = compare(fresh, _bench_doc(tp=100.0))
        assert v["passed"]
        assert any("baseline gates skipped" in w for w in v["warnings"])

    @staticmethod
    def _pallas_doc(**over):
        m = {
            "resolved_backend": "pallas-interpret",
            "batches": [{"ensemble": 1, "farm_steps_per_s": 100.0},
                        {"ensemble": 4, "farm_steps_per_s": 300.0}],
            "parity": {"ok": True},
            "expected_compile_misses": 3,
            "compile_cache": {"misses": 3, "hits": 1, "entries": 3},
        }
        m.update(over)
        return {"schema": obs.BENCH_SCHEMA, "bench": "ensemble_pallas",
                "passed": True,
                "host": {"backend": "cpu", "device_count": 1},
                "metrics": m}

    def test_pallas_structural_gate_passes_clean_doc(self):
        v = compare(self._pallas_doc(), None)
        assert v["passed"], v["failures"]

    def test_pallas_parity_break_fails_without_baseline(self):
        v = compare(self._pallas_doc(parity={"ok": False}), None)
        assert not v["passed"]
        assert any("parity did not hold" in f for f in v["failures"])

    def test_pallas_per_scalar_recompile_fails(self):
        """Five scalars fragmenting into five executables is THE failure
        mode the scalar table exists to prevent."""
        v = compare(self._pallas_doc(
            compile_cache={"misses": 7, "hits": 0, "entries": 7}), None)
        assert not v["passed"]
        assert any("per-scalar recompile" in f for f in v["failures"])

    def test_pallas_wrong_backend_fails(self):
        v = compare(self._pallas_doc(resolved_backend="jnp"), None)
        assert not v["passed"]
        assert any("not a pallas backend" in f for f in v["failures"])

    def test_smoke_docs_skip_the_pallas_gate(self):
        # the structural gate keys on the bench name, not on field absence
        assert compare(_bench_doc(), None)["passed"]

    def test_committed_baseline_is_valid(self):
        """The file CI gates against must itself load, validate, and
        carry a well-formed perf block."""
        import os

        path = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmarks", "baselines",
            "BENCH_smoke.json")
        doc = obs.load_bench(path)
        perf.validate_perf(doc["metrics"]["perf"])
        assert doc["passed"] is True


# ---------------------------------------------------------------------------
# health-overhead gate: the modeled monitor cost, not a wall-clock ratio
# ---------------------------------------------------------------------------
def _health_doc(model=..., *, drains=2, boundaries=2, health_tp=100.0):
    if model is ...:
        model = {"status": "ok", "check_every": 8,
                 "hbm_bytes_step": 8e5, "hbm_bytes_step_health": 8.6e5,
                 "hbm_bytes_diag_per_chunk": 6e4,
                 "modeled_overhead": 0.0094}
    doc = _bench_doc()
    health = {"drains": drains, "boundaries": boundaries}
    if model is not None:
        health["model"] = model
    doc["metrics"]["health"] = health
    doc["metrics"]["steady_sim_steps_per_s_checked"] = 100.0
    doc["metrics"]["steady_sim_steps_per_s_health"] = health_tp
    return doc


class TestHealthOverheadGate:
    def test_modeled_overhead_within_bound_passes(self):
        # wall-clock pair 30% apart: recorded but NOT gated — only the
        # deterministic model binds
        v = compare(_health_doc(health_tp=70.0), None)
        assert v["passed"], v["failures"]

    def test_modeled_overhead_over_bound_fails(self):
        doc = _health_doc(dict(_health_doc()["metrics"]["health"]["model"],
                               modeled_overhead=0.08))
        v = compare(doc, None)
        assert not v["passed"]
        assert any("modeled health overhead" in f for f in v["failures"])

    def test_unparsed_model_fails(self):
        doc = _health_doc({"status": "unparsed", "error": "boom",
                           "modeled_overhead": None})
        v = compare(doc, None)
        assert not v["passed"]
        assert any("cost model unparsed" in f for f in v["failures"])

    def test_dropped_model_with_health_throughput_fails(self):
        """An artifact that records health throughput but no model means
        the gate was silently disconnected — fail, don't bootstrap."""
        v = compare(_health_doc(None), None)
        assert not v["passed"]
        assert any("no health.model" in f for f in v["failures"])

    def test_off_cadence_drain_fails(self):
        v = compare(_health_doc(drains=3, boundaries=2), None)
        assert not v["passed"]
        assert any("harvest boundaries" in f for f in v["failures"])

    def test_docs_without_health_block_bootstrap(self):
        assert compare(_bench_doc(), None)["passed"]

    def test_model_on_real_executables_is_deterministic_and_small(self):
        """The number the gate binds on, computed twice from the real
        lowered farm executables: bit-identical across calls (the whole
        point — wall-clock is not) and within the 3% bound."""
        def executor(health):
            rt = api.runtime(n=N, n_slots=2, health=health,
                             check_every=8, **KW)
            rt.submit("cavity", re=100.0, steps=4)
            rt.drain()
            return next(iter(rt._services.values())).farm.exec

        ex_off, ex_on = executor(False), executor(True)
        a = perf.health_overhead_model(ex_off, ex_on, 8)
        b = perf.health_overhead_model(ex_off, ex_on, 8)
        assert a == b
        assert a["status"] == "ok"
        assert 0.0 < a["modeled_overhead"] <= 0.03
        assert a["hbm_bytes_diag_per_chunk"] > 0
        assert compare(_health_doc(a), None)["passed"]


# ---------------------------------------------------------------------------
# durability-smoke gate: kill-and-resume invariants, baseline-free
# ---------------------------------------------------------------------------
def _durability_doc(**over):
    m = {"jobs": 4, "killed": True, "orphaned_ok": True,
         "incomplete_at_restart": 3, "resumed": 3, "resumed_first": True,
         "lease_takeovers": 3, "single_execution": True, "all_done": True,
         "parity_ok": True,
         "store_counts": {"queued": 0, "running": 0, "evicted": 0,
                          "done": 4, "failed": 0, "diverged": 0}}
    m.update(over)
    return {"schema": obs.BENCH_SCHEMA, "bench": "durability_smoke",
            "passed": True,
            "host": {"backend": "cpu", "device_count": 1},
            "metrics": m}


class TestDurabilitySmokeGate:
    def test_clean_doc_passes_without_baseline(self):
        v = compare(_durability_doc(), None)
        assert v["passed"], v["failures"]

    def test_not_killed_fails(self):
        v = compare(_durability_doc(killed=False), None)
        assert not v["passed"]
        assert any("SIGKILLed" in f for f in v["failures"])

    def test_no_resume_fails(self):
        v = compare(_durability_doc(resumed=0), None)
        assert not v["passed"]
        assert any("resumed no" in f for f in v["failures"])

    def test_queued_before_incomplete_fails(self):
        v = compare(_durability_doc(resumed_first=False), None)
        assert not v["passed"]
        assert any("resume-first" in f for f in v["failures"])

    def test_double_execution_fails(self):
        v = compare(_durability_doc(single_execution=False), None)
        assert not v["passed"]
        assert any("double execution" in f for f in v["failures"])

    def test_undrained_queue_fails(self):
        v = compare(_durability_doc(all_done=False), None)
        assert not v["passed"]
        assert any("drain" in f for f in v["failures"])

    def test_parity_break_fails(self):
        v = compare(_durability_doc(parity_ok=False), None)
        assert not v["passed"]
        assert any("bitwise" in f for f in v["failures"])

    def test_other_smokes_skip_this_gate(self):
        # keys on the bench name: a plain smoke doc with none of these
        # metrics must not trip the durability invariants
        assert compare(_bench_doc(), None)["passed"]
