"""repro.obs: timer-nesting invariants, metrics round-trips, per-sim trace
ordering (failed sims included), Chrome-trace schema, the bench-document
schema, watchdog wiring, compile-cache scoping, the program's profiler
spans and stage scopes — and the frozen contract that telemetry off is
bitwise-invisible."""
import glob
import json
import re
import threading

import jax
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro import api, obs
from repro.cfd import cavity
from repro.sim import SimulationService, reset_compile_cache
from repro.sim.farm import compile_cache_stats

N = 12
KW = dict(jacobi_iters=8)


class _FakeClock:
    """Deterministic clock: every read advances by one tick."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 1.0
        return self.t


# ---------------------------------------------------------------------------
# timers
# ---------------------------------------------------------------------------
class TestTimers:
    def test_nesting_accumulates(self):
        tree = obs.TimerTree(clock=_FakeClock())
        for _ in range(3):
            with tree.section("outer"):
                with tree.section("inner"):
                    pass
        snap = tree.snapshot()
        assert snap["outer"]["count"] == 3
        assert snap["outer"]["children"]["inner"]["count"] == 3
        assert snap["outer"]["children"]["inner"]["total_s"] <= \
            snap["outer"]["total_s"]

    @settings(max_examples=25)
    @given(ops=st.lists(st.integers(min_value=0, max_value=9), max_size=40))
    def test_child_totals_bounded_by_parent(self, ops):
        """Cactus timer invariant: once every section is closed, the sum
        of any node's direct children's totals never exceeds the node's
        own total (children run inside the parent's open interval)."""
        tree = obs.TimerTree(clock=_FakeClock())
        stack = []
        for op in ops:
            if op % 2 == 0 or not stack:   # open a (cycling) section name
                cm = tree.section(f"s{op % 3}")
                cm.__enter__()
                stack.append(cm)
            else:                          # close the innermost
                stack.pop().__exit__(None, None, None)
        while stack:
            stack.pop().__exit__(None, None, None)

        def check(node):
            child_sum = sum(c["total_s"] for c in node["children"].values())
            assert child_sum <= node["total_s"] + 1e-9
            for c in node["children"].values():
                check(c)

        for root in tree.snapshot().values():
            check(root)

    def test_report_renders_all_sections(self):
        tree = obs.TimerTree(clock=_FakeClock())
        with tree.section("a"), tree.section("b"):
            pass
        text = tree.report()
        assert "a" in text and "b" in text and "count" in text

    def test_threaded_sections_stay_separated(self):
        tree = obs.TimerTree()

        def work(name):
            for _ in range(50):
                with tree.section(name):
                    with tree.section(f"{name}.child"):
                        pass

        ts = [threading.Thread(target=work, args=(f"t{i}",))
              for i in range(4)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        snap = tree.snapshot()
        assert set(snap) == {f"t{i}" for i in range(4)}
        for i in range(4):
            assert snap[f"t{i}"]["count"] == 50


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------
class TestMetrics:
    def test_labeled_series_round_trip_through_json(self):
        reg = obs.Registry()
        reg.inc("farm.compile_cache", result="hit")
        reg.inc("farm.compile_cache", 2, result="miss")
        reg.set("farm.queue_depth", 3, priority=1)
        for v in (0.01, 0.2, 0.2, 5.0):
            reg.observe("latency", v, priority=0)
        snap = json.loads(reg.to_json())
        assert snap == reg.snapshot()
        assert snap["counters"]["farm.compile_cache{result=hit}"] == 1
        assert snap["counters"]["farm.compile_cache{result=miss}"] == 2
        assert snap["gauges"]["farm.queue_depth{priority=1}"] == 3.0
        h = snap["histograms"]["latency{priority=0}"]
        assert h["count"] == 4 and h["min"] == 0.01 and h["max"] == 5.0
        assert sum(n for _, n in h["buckets"]) == 4

    def test_series_key_is_label_order_insensitive(self):
        assert obs.series_key("m", {"b": 1, "a": 2}) == "m{a=2,b=1}"

    def test_histogram_percentiles(self):
        h = obs.Histogram()
        for v in [0.001] * 90 + [1.0] * 10:
            h.observe(v)
        assert h.percentile(50) <= 0.01
        assert h.percentile(99) >= 0.5

    def test_concurrent_increments_are_not_lost(self):
        reg = obs.Registry()

        def bump():
            for _ in range(1000):
                reg.inc("n")

        ts = [threading.Thread(target=bump) for _ in range(8)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert reg.get("n") == 8000


# ---------------------------------------------------------------------------
# traces: lifecycle ordering + chrome export
# ---------------------------------------------------------------------------
class TestTraces:
    @pytest.fixture(scope="class")
    def traced_farm(self):
        """A drained farm with healthy sims AND an admission failure."""
        tel = obs.telemetry()
        svc = SimulationService(cavity.config(N, **KW), n_slots=2,
                                telemetry=tel)
        sids = [svc.submit(cavity.sim_request(N, re=re, steps=s, **KW))
                for re, s in ((80.0, 8), (160.0, 12), (240.0, 6))]
        bad = cavity.sim_request(N, re=320.0, steps=5, **KW)
        bad.init_state = {"vx": np.zeros((2, 2, 2), np.float32)}
        sids.append(svc.submit(bad))
        svc.drain()
        return tel, sids

    def test_per_sim_lifecycle_ordering(self, traced_farm):
        """submit < admit < result for every sid — failed sims included;
        healthy sims additionally record first_step between them."""
        tel, sids = traced_farm
        for sid in sids:
            events = tel.trace.events_for(sid)
            seq = {e["kind"]: e["seq"] for e in events}
            assert {"submit", "admit", "result"} <= set(seq), events
            assert seq["submit"] < seq["admit"] < seq["result"]
            ts = [e["ts"] for e in events]
            assert ts == sorted(ts)

    def test_failed_sim_result_carries_error(self, traced_farm):
        tel, sids = traced_farm
        failed = [e for e in tel.trace.events
                  if e["kind"] == "result" and e.get("terminated") == "failed"]
        assert len(failed) == 1
        assert failed[0]["sid"] == sids[-1] and failed[0]["error"]

    def test_chrome_export_validates_and_spans_slots(self, traced_farm):
        tel, sids = traced_farm
        doc = obs.validate_chrome_trace(tel.trace.to_chrome())
        evs = doc["traceEvents"]
        # one residency span per admitted sim, on the slot track
        spans = [e for e in evs if e["ph"] == "X"]
        assert len(spans) == len(sids)
        assert all(e["dur"] >= 0 and e["pid"] == 2 for e in spans)
        # instants carry the sid track and the original payload
        submits = [e for e in evs if e["name"] == "submit"]
        assert {e["tid"] for e in submits} == set(sids)
        assert all("signature" in e["args"] for e in submits)

    def test_chrome_schema_rejects_malformed(self):
        with pytest.raises(ValueError, match="traceEvents"):
            obs.validate_chrome_trace({"events": []})
        with pytest.raises(ValueError, match="missing 'dur'"):
            obs.validate_chrome_trace({"traceEvents": [
                {"name": "x", "ph": "X", "ts": 0, "pid": 1, "tid": 0}]})
        with pytest.raises(ValueError, match="unknown phase"):
            obs.validate_chrome_trace({"traceEvents": [
                {"name": "x", "ph": "Z", "ts": 0, "pid": 1, "tid": 0}]})

    def test_jsonl_stream_is_line_per_event(self, tmp_path):
        path = str(tmp_path / "events.jsonl")
        tel = obs.telemetry(trace_path=path)
        tel.trace.emit("submit", sid=0, tag="t")
        tel.trace.emit("result", sid=0, terminated="steps")
        tel.trace.close()
        lines = [json.loads(line) for line in
                 open(path).read().splitlines()]
        assert [e["kind"] for e in lines] == ["submit", "result"]
        assert lines[0]["sid"] == 0


# ---------------------------------------------------------------------------
# telemetry-off is bitwise-invisible
# ---------------------------------------------------------------------------
class TestBitwiseInvisible:
    def test_farm_results_identical_on_vs_off(self):
        jobs = ((70.0, 9), (150.0, 14), (300.0, 7))

        def run(telemetry):
            rt = api.runtime(n=N, n_slots=2, telemetry=telemetry, **KW)
            sids = [rt.submit("cavity", re=re, steps=s)
                    for re, s in jobs]
            out = rt.drain()
            return [out[s] for s in sids]

        on, off = run(True), run(False)
        for a, b in zip(on, off):
            assert a.steps_done == b.steps_done
            for f in ("vx", "vy", "vz", "p"):
                np.testing.assert_array_equal(a.state[f], b.state[f])

    def test_serial_run_identical_on_vs_off(self):
        res = [api.runtime(n=N, telemetry=t, **KW).run(
            "cavity", re=120.0, steps=10) for t in (True, False)]
        for f in ("vx", "vy", "vz", "p"):
            np.testing.assert_array_equal(res[0].state[f], res[1].state[f])

    def test_off_runtime_uses_null_telemetry(self):
        rt = api.runtime(n=N, **KW)
        assert rt.telemetry is obs.NULL and not rt.telemetry.enabled
        # every hook degrades to a no-op
        with rt.telemetry.section("x"):
            pass
        rt.telemetry.metrics.inc("x")
        assert rt.telemetry.metrics.snapshot()["counters"] == {}


# ---------------------------------------------------------------------------
# farm/runtime telemetry content
# ---------------------------------------------------------------------------
class TestFarmTelemetry:
    @pytest.fixture(scope="class")
    def run_rt(self):
        rt = api.runtime(n=N, n_slots=2, telemetry=True, **KW)
        sids = [rt.submit("cavity", re=re, steps=10, priority=p)
                for re, p in ((90.0, 0), (180.0, 1), (270.0, 0))]
        rt.drain()
        return rt, sids

    def test_timers_cover_the_farm_phases(self, run_rt):
        rt, _ = run_rt
        snap = rt.telemetry.timers.snapshot()
        assert {"farm.admit", "farm.step_chunk", "farm.harvest"} <= set(snap)
        assert snap["farm.step_chunk"]["count"] >= 1
        assert "ensemble.write_slots" in snap["farm.admit"]["children"]
        assert "ensemble.read_slots" in snap["farm.harvest"]["children"]

    def test_metrics_cover_the_farm_load(self, run_rt):
        rt, sids = run_rt
        m = rt.telemetry.metrics
        assert m.get("sim.steps_total") == 10 * len(sids)
        assert m.get("sim.results", terminated="steps") == len(sids)
        assert m.get("farm.slot_occupancy") == 0.0   # drained
        h = m.get("service.submit_to_result_seconds", priority=0)
        assert h is not None and h.count == 2
        assert m.get("service.submit_to_result_seconds", priority=1).count \
            == 1

    def test_report_is_human_readable(self, run_rt):
        rt, _ = run_rt
        text = rt.report()
        assert "repro.obs report" in text
        assert "farm.step_chunk" in text and "sim.steps_total" in text
        assert obs.report(rt.telemetry) == text

    def test_schedule_bins_are_timed_on_serial_runs(self):
        rt = api.runtime(n=N, telemetry=True, **KW)
        rt.run("cavity", re=100.0, steps=6)
        snap = rt.telemetry.timers.snapshot()
        assert "schedule.INITIAL" in snap
        evolve = snap["run.cavity"]["children"]["schedule.EVOL"]
        assert evolve["count"] == 6
        assert "ns3d_step" in evolve["children"]


# ---------------------------------------------------------------------------
# compile-cache lifecycle: scoped to the runtime's registry
# ---------------------------------------------------------------------------
class TestCompileCacheScoping:
    def test_back_to_back_runtimes_report_their_own_hits(self):
        """The satellite fix: a second runtime of the same signature sees
        ITS one cache hit, not the first runtime's miss — while the
        legacy module facade keeps accumulating process-wide."""
        reset_compile_cache()
        rt1 = api.runtime(n=N, n_slots=2, telemetry=True, **KW)
        rt1.submit("cavity", re=100.0, steps=2)
        rt1.drain()
        assert compile_cache_stats(rt1.telemetry.metrics) == {
            "hits": 0, "misses": 1, "entries": 1}
        rt2 = api.runtime(n=N, n_slots=2, telemetry=True, **KW)
        rt2.submit("cavity", re=200.0, steps=2)
        rt2.drain()
        assert compile_cache_stats(rt2.telemetry.metrics) == {
            "hits": 1, "misses": 0, "entries": 1}
        # rt1's scoped view did not absorb rt2's traffic
        assert compile_cache_stats(rt1.telemetry.metrics)["hits"] == 0
        facade = compile_cache_stats()
        assert facade["hits"] == 1 and facade["misses"] == 1

    def test_facade_reset_still_works(self):
        reset_compile_cache()
        assert compile_cache_stats() == {"hits": 0, "misses": 0,
                                         "entries": 0}


# ---------------------------------------------------------------------------
# watchdog wiring (ft.watchdog -> service)
# ---------------------------------------------------------------------------
class TestWatchdog:
    def test_stall_metric_and_trace_on_missed_deadline(self):
        """With a zero heartbeat deadline every inter-beat gap is a
        'missed deadline': the stall counter and trace event must fire."""
        tel = obs.telemetry(heartbeat_deadline_s=0.0)
        svc = SimulationService(cavity.config(N, **KW), n_slots=2,
                                telemetry=tel)
        sid = svc.submit(cavity.sim_request(N, re=100.0, steps=6, **KW))
        svc.result(sid)
        svc.poll(sid)
        assert tel.metrics.get("service.watchdog_stalls") >= 1
        assert any(e["kind"] == "watchdog_stall" for e in tel.trace.events)

    def test_no_stalls_under_generous_deadline(self):
        tel = obs.telemetry(heartbeat_deadline_s=3600.0)
        svc = SimulationService(cavity.config(N, **KW), n_slots=2,
                                telemetry=tel)
        sid = svc.submit(cavity.sim_request(N, re=100.0, steps=6, **KW))
        svc.result(sid)
        assert tel.metrics.get("service.watchdog_stalls") is None
        # but the step watchdog did observe every chunk
        assert svc.watchdog is not None and svc.watchdog.n >= 1

    def test_heartbeat_file_is_touched(self, tmp_path):
        hb = str(tmp_path / "alive")
        tel = obs.telemetry(heartbeat_path=hb, heartbeat_interval_s=0.0)
        svc = SimulationService(cavity.config(N, **KW), n_slots=1,
                                telemetry=tel)
        sid = svc.submit(cavity.sim_request(N, re=100.0, steps=3, **KW))
        svc.result(sid)
        from repro.ft.watchdog import Heartbeat

        assert Heartbeat.is_alive(hb, deadline_s=60.0)

    def test_disabled_telemetry_installs_no_watchdog(self):
        svc = SimulationService(cavity.config(N, **KW), n_slots=1)
        assert svc.watchdog is None and svc.farm.heartbeat is None


# ---------------------------------------------------------------------------
# bench document schema
# ---------------------------------------------------------------------------
class TestBenchSchema:
    def test_round_trip(self, tmp_path):
        doc = obs.make_bench_doc("ensemble_farm", {"speedup": 2.5},
                                 passed=True, wall_s=1.25)
        path = obs.write_bench(doc, str(tmp_path))
        assert path.endswith("BENCH_ensemble_farm.json")
        loaded = obs.load_bench(path)
        assert loaded["metrics"]["speedup"] == 2.5
        assert loaded["schema"] == obs.BENCH_SCHEMA
        for f in ("backend", "device_kind", "device_count", "python", "jax"):
            assert f in loaded["host"]
        assert loaded["host"]["device_kind"] == jax.devices()[0].device_kind

    def test_malformed_documents_are_named(self):
        good = obs.make_bench_doc("x", {}, passed=False, wall_s=0.0)
        for breakage, match in (
                ({"schema": "repro.bench.v0"}, "schema"),
                ({"bench": "Bad Name"}, "must match"),
                ({"passed": "yes"}, "passed"),
                ({"host": {"backend": "cpu"}}, "host missing"),
        ):
            with pytest.raises(ValueError, match=match):
                obs.validate_bench({**good, **breakage})
        with pytest.raises(ValueError, match="missing field"):
            obs.validate_bench({k: v for k, v in good.items()
                                if k != "metrics"})

    def test_smoke_bench_emits_valid_artifact(self, tmp_path):
        """The CI smoke lane end-to-end: run the telemetry bench, check
        the artifact on disk validates and carries the telemetry
        snapshot."""
        from benchmarks.run import run_smoke

        doc = run_smoke(str(tmp_path))
        assert doc["passed"] is True
        loaded = obs.load_bench(str(tmp_path / "BENCH_smoke.json"))
        assert loaded["bench"] == "smoke"
        assert "timers" in loaded["metrics"]["telemetry"]
        assert loaded["metrics"]["compile_cache"]["entries"] >= 1


# ---------------------------------------------------------------------------
# health: unit layer (state machine, flight records, dashboard)
# ---------------------------------------------------------------------------
def _frame(step=0, div=0.0, ke=0.1, umax=1.0, cfl=0.1, finite=1.0):
    return {"step": step, "div_linf": div, "ke": ke, "umax": umax,
            "cfl": cfl, "finite": finite}


def _row(step, div=0.0, ke=0.1, umax=1.0, cfl=0.1, finite=1.0):
    return [float(step), div, ke, umax, cfl, finite]


class TestHealthUnit:
    def test_diag_columns_pin_the_solver_contract(self):
        """obs.health and ns3d each own a copy of the diagnostics name
        tuple (the solver owes nothing to obs); this is the pin that
        keeps them from drifting apart."""
        from repro.cfd import ns3d
        from repro.obs import health

        assert health.DIAG_COLUMNS == ("step",) + ns3d.HEALTH_DIAGS
        assert health.N_DIAG == len(health.DIAG_COLUMNS)

    def test_classify_frame_thresholds(self):
        from repro.obs import health

        cfg = health.HealthConfig()
        assert health.classify_frame(_frame(), cfg) == (health.HEALTHY, "")
        assert health.classify_frame(_frame(cfl=2.5), cfg) == \
            (health.WARNING, "cfl")
        assert health.classify_frame(_frame(div=1e4), cfg) == \
            (health.WARNING, "divergence")
        assert health.classify_frame(_frame(div=1e8), cfg) == \
            (health.DIVERGED, "divergence")
        assert health.classify_frame(_frame(cfl=1e4), cfg) == \
            (health.DIVERGED, "cfl")
        assert health.classify_frame(_frame(finite=0.0), cfg) == \
            (health.NAN, "nonfinite")
        # a NaN that leaks into the diagnostics themselves is nonfinite
        assert health.classify_frame(_frame(div=float("nan")), cfg) == \
            (health.NAN, "nonfinite")

    def test_monitor_warning_recovers_but_terminal_sticks(self):
        from repro.obs import health

        mon = health.HealthMonitor(health.HealthConfig())
        mon.admit(7, slot=0, tag="t")
        assert mon.observe(7, np.array([_row(0, cfl=3.0)])).state \
            == health.WARNING
        assert mon.observe(7, np.array([_row(1)])).state == health.HEALTHY
        assert mon.observe(7, np.array([_row(2, finite=0.0)])).state \
            == health.NAN
        # terminal: later healthy frames cannot resurrect the record
        assert mon.observe(7, np.array([_row(3)])).state == health.NAN

    def test_monitor_skips_sentinels_and_stale_steps(self):
        from repro.obs import health

        mon = health.HealthMonitor(health.HealthConfig(window=4))
        mon.admit(1, slot=0)
        rec = mon.observe(1, np.array([_row(-1), _row(2), _row(0), _row(1)]))
        assert [f["step"] for f in rec.frames] == [0, 1, 2]
        # a re-drain of the same ring adds nothing
        rec = mon.observe(1, np.array([_row(2), _row(0), _row(1)]))
        assert [f["step"] for f in rec.frames] == [0, 1, 2]

    def test_monitor_emits_trace_and_metrics_on_transition(self):
        from repro.obs import health

        tel = obs.telemetry()
        mon = health.HealthMonitor(health.HealthConfig(), telemetry=tel,
                                   farm_id="f0")
        mon.admit(3, slot=1, tag="x")
        mon.observe(3, np.array([_row(0, div=1e8)]))
        evs = [e for e in tel.trace.events if e["kind"] == "health"]
        assert len(evs) == 1
        ev = evs[0]
        assert ev["sid"] == 3 and ev["farm"] == "f0" and ev["slot"] == 1
        assert ev["state"] == "diverged" and ev["from"] == "healthy"
        assert ev["cause"] == "divergence" and ev["frame"]["step"] == 0
        assert tel.metrics.get("health.events", state="diverged",
                               cause="divergence") == 1

    def test_mark_shares_the_event_schema(self):
        from repro.obs import health

        tel = obs.telemetry()
        mon = health.HealthMonitor(health.HealthConfig(), telemetry=tel)
        mon.admit(5, slot=0)
        mon.mark(5, health.WARNING, cause="watchdog_stall", gap_s=1.5)
        ev = [e for e in tel.trace.events if e["kind"] == "health"][0]
        assert ev["state"] == "warning" and ev["cause"] == "watchdog_stall"
        assert ev["gap_s"] == 1.5
        assert mon.state_of(5) == health.WARNING

    def test_registry_remove_drops_the_series(self):
        reg = obs.Registry()
        reg.set("health.sim_state", 2.0, sid=9)
        reg.inc("health.frames")
        assert reg.remove("health.sim_state", sid=9) is True
        assert reg.get("health.sim_state", sid=9) is None
        assert reg.remove("health.sim_state", sid=9) is False
        assert reg.get("health.frames") == 1   # other series untouched

    def test_flight_record_round_trip(self, tmp_path):
        from repro.obs import health

        fr = health.FlightRecorder(str(tmp_path))
        frames = np.arange(18, dtype=np.float32).reshape(3, 6)
        state = {"vx": np.ones((2, 3, 4), np.float32),
                 "p": np.zeros((2, 3, 4), np.float32)}
        path = fr.record(11, frames=frames, state=state,
                         meta={"cause": "cfl", "tag": "poison"})
        assert path.endswith("step_00000011")
        rec = health.load_flight_record(str(tmp_path), 11)
        np.testing.assert_array_equal(rec["frames"], frames)
        assert set(rec["state"]) == {"vx", "p"}
        np.testing.assert_array_equal(rec["state"]["vx"], state["vx"])
        assert rec["meta"]["cause"] == "cfl"
        assert rec["meta"]["columns"] == list(health.DIAG_COLUMNS)

    def test_resolve_health_specs(self):
        from repro.obs import health

        assert health.resolve_health(None) is None
        assert health.resolve_health(False) is None
        assert health.resolve_health(True) == health.HealthConfig()
        cfg = health.HealthConfig(window=4)
        assert health.resolve_health(cfg) is cfg
        assert health.resolve_health({"cfl_warn": 5.0}).cfl_warn == 5.0
        with pytest.raises(TypeError):
            health.resolve_health(42)


# ---------------------------------------------------------------------------
# health: NaN-injection battery (quarantine, flight record, bitwise twins)
# ---------------------------------------------------------------------------
HEALTH_JOBS = ((80.0, "h0"), (150.0, "h1"), (240.0, "h2"))


def _health_runtime(ckpt_dir, telemetry=True):
    return api.runtime(n=N, n_slots=4, check_every=8, ckpt_dir=ckpt_dir,
                       health=True, telemetry=telemetry, **KW)


def _submit_healthy(rt):
    return [rt.submit("cavity", re=re, steps=24, tag=tag)
            for re, tag in HEALTH_JOBS]


class TestHealthQuarantine:
    @pytest.fixture(scope="class")
    def quarantine_run(self, tmp_path_factory):
        """A drained health-monitored farm: 3 healthy cavity sims plus
        one poisoned with a huge dt (slot-parameterized, so no separate
        compile) that blows past the CFL-diverged threshold."""
        tmp = str(tmp_path_factory.mktemp("health"))
        rt = _health_runtime(tmp)
        healthy = _submit_healthy(rt)
        bad = rt.submit("cavity", re=100.0, steps=24, dt=50.0, tag="poison")
        res = rt.drain()
        return rt, healthy, bad, res, tmp

    def test_poisoned_slot_quarantines(self, quarantine_run):
        rt, healthy, bad, res, _ = quarantine_run
        r = res[bad]
        assert r.terminated == "diverged"
        assert r.steps_done < 24
        assert "health: " in r.error and "flight record" in r.error
        assert rt.poll(bad)["status"] == "diverged"
        for sid in healthy:
            assert res[sid].terminated == "steps"
            assert res[sid].steps_done == 24

    def test_flight_record_is_readable_post_mortem(self, quarantine_run):
        from repro.obs import health

        rt, _, bad, res, tmp = quarantine_run
        inner = rt._routes[bad][1]
        rec = health.load_flight_record(f"{tmp}/flight", inner)
        frames = rec["frames"]
        assert frames.shape[1] == health.N_DIAG
        assert 1 <= frames.shape[0] <= health.HealthConfig().window
        # the recorded tail must contain the killing frame
        cfl = frames[:, health.DIAG_COLUMNS.index("cfl")]
        finite = frames[:, health.DIAG_COLUMNS.index("finite")]
        assert (cfl[np.isfinite(cfl)] >= 1e3).any() or (finite < 0.5).any()
        assert {"vx", "vy", "vz", "p"} <= set(rec["state"])
        meta = rec["meta"]
        assert meta["state"] in ("diverged", "nan") and meta["cause"]
        assert meta["tag"] == "poison" and "thresholds" in meta

    def test_healthy_slots_bitwise_vs_never_admitted(self, quarantine_run,
                                                     tmp_path):
        """The quarantine isolation contract: slots that shared a farm
        with the poisoned sim finish bitwise-identical to a farm that
        never admitted it (same slot assignment: healthy submitted
        first)."""
        _, healthy, _, res, _ = quarantine_run
        rt2 = _health_runtime(str(tmp_path))
        twins = _submit_healthy(rt2)
        res2 = rt2.drain()
        for a, b in zip(healthy, twins):
            for f in ("vx", "vy", "vz", "p"):
                np.testing.assert_array_equal(res[a].state[f],
                                              res2[b].state[f])

    def test_zero_extra_host_syncs_on_harvest_cadence(self, quarantine_run):
        """The perf pin: ring drains ride the existing
        check_steady_every boundary — drains == boundaries crossed, and
        the farm cost row books exactly that."""
        from repro.obs import perf

        rt, _, _, _, _ = quarantine_run
        svc = next(iter(rt._services.values()))
        boundaries = svc.farm.device_steps // svc.farm.check_steady_every
        assert svc.farm.device_steps % svc.farm.check_steady_every == 0
        assert rt.telemetry.metrics.get("health.drains") == boundaries
        timers = rt.telemetry.timers.snapshot()
        drain_s, drain_n = perf._find_sections(timers, "farm.health_drain")
        assert drain_n == boundaries
        row = perf.farm_cost_row(svc)
        assert row.health_drains == boundaries
        assert row.health_boundaries == boundaries
        rendered = perf.PerfReport([row]).render()
        assert "extra host syncs: 0" in rendered

    def test_health_events_join_the_trace(self, quarantine_run):
        rt, _, bad, _, _ = quarantine_run
        inner = rt._routes[bad][1]
        evs = rt.telemetry.trace.events_for(inner)
        kinds = [e["kind"] for e in evs]
        assert "health" in kinds and "result" in kinds
        health_ev = next(e for e in evs if e["kind"] == "health")
        assert health_ev["state"] in ("diverged", "nan")
        result_ev = next(e for e in evs if e["kind"] == "result")
        assert result_ev["terminated"] == "diverged"
        assert rt.telemetry.metrics.get("health.quarantines") == 1
        assert rt.telemetry.metrics.get(
            "sim.results", terminated="diverged") == 1

    def test_chrome_export_puts_health_on_its_own_track(self, quarantine_run):
        rt, _, _, _, _ = quarantine_run
        doc = obs.validate_chrome_trace(rt.telemetry.trace.to_chrome())
        evs = doc["traceEvents"]
        health_evs = [e for e in evs if e["ph"] == "i"
                      and e["name"] == "health"]
        assert health_evs and all(e["pid"] == 3 for e in health_evs)
        assert any(e.get("args", {}).get("name") == "health"
                   for e in evs if e["ph"] == "M")
        # the quarantined sim still closes a residency span on the slot
        # track — 4 admissions, 4 spans
        assert len([e for e in evs if e["ph"] == "X"]) == 4

    def test_prometheus_exposes_health_series(self, quarantine_run):
        rt, _, _, _, _ = quarantine_run
        svc = next(iter(rt._services.values()))
        text = svc.prometheus_text()
        assert "repro_health_quarantines 1" in text
        assert "repro_health_drains" in text
        assert 'repro_health_sims{state="healthy"}' in text
        assert 'repro_health_events{' in text

    def test_watch_renders_the_dashboard(self, quarantine_run):
        rt, _, _, _, _ = quarantine_run
        text = rt.watch()
        assert "== repro health ==" in text
        assert "slot" in text and "free" in text   # drained farm

    def test_quarantine_works_with_telemetry_off(self, quarantine_run,
                                                 tmp_path):
        """Health is functional, not telemetry: with telemetry off the
        quarantine still fires, the flight record still lands, and the
        healthy trajectories are bitwise the telemetry-on ones."""
        from repro.obs import health

        _, healthy, _, res_on, _ = quarantine_run
        rt = _health_runtime(str(tmp_path), telemetry=False)
        assert rt.telemetry is obs.NULL
        twins = _submit_healthy(rt)
        bad = rt.submit("cavity", re=100.0, steps=24, dt=50.0, tag="poison")
        res = rt.drain()
        assert res[bad].terminated == "diverged"
        rec = health.load_flight_record(f"{tmp_path}/flight",
                                        rt._routes[bad][1])
        assert rec["meta"]["tag"] == "poison"
        for a, b in zip(healthy, twins):
            for f in ("vx", "vy", "vz", "p"):
                np.testing.assert_array_equal(res_on[a].state[f],
                                              res[b].state[f])

    def test_poll_streams_the_latest_frame_while_running(self):
        svc = SimulationService(cavity.config(N, **KW), n_slots=1,
                                check_steady_every=4, telemetry=True,
                                health=True)
        sid = svc.submit(cavity.sim_request(N, re=100.0, steps=12, **KW))
        svc.run(4)
        out = svc.poll(sid)
        assert out["status"] == "running" and out["steps_done"] == 4
        h = out["health"]
        assert h["state"] == "healthy" and h["step"] == 3
        assert all(np.isfinite(h[c]) for c in ("div_linf", "ke", "cfl"))
        from repro.obs.health import render_dashboard

        text = render_dashboard([svc.farm.health_snapshot()])
        assert "ok" in text and "cavity" in text
        svc.drain()

    def test_watchdog_stall_marks_resident_sims_warning(self):
        """Satellite: a watchdog stall speaks the health vocabulary —
        resident sims go ``warning`` with the same kind="health" trace
        schema as quarantine (and recover on the next healthy drain)."""
        tel = obs.telemetry(heartbeat_deadline_s=0.0)
        svc = SimulationService(cavity.config(N, **KW), n_slots=2,
                                check_steady_every=2, telemetry=tel,
                                health=True)
        sid = svc.submit(cavity.sim_request(N, re=100.0, steps=6, **KW))
        svc.result(sid)
        evs = [e for e in tel.trace.events if e["kind"] == "health"
               and e["cause"] == "watchdog_stall"]
        assert evs and evs[0]["state"] == "warning" and "gap_s" in evs[0]
        # the sim recovered and finished: warning -> healthy also traced
        recoveries = [e for e in tel.trace.events if e["kind"] == "health"
                      and e["state"] == "healthy" and e["from"] == "warning"]
        assert recoveries

    def test_health_off_runs_the_pre_health_executable(self):
        """health=False compiles the exact PR-8 step signature: no ring,
        no step counter, no monitor — and drain results match a
        health-on farm bitwise (diagnostics are read-only)."""
        def run(health):
            rt = api.runtime(n=N, n_slots=2, health=health, **KW)
            sids = [rt.submit("cavity", re=re, steps=10)
                    for re, _ in HEALTH_JOBS[:2]]
            out = rt.drain()
            svc = next(iter(rt._services.values()))
            return [out[s] for s in sids], svc.farm.exec

        off, ex_off = run(False)
        on, ex_on = run(True)
        assert ex_off.health_ring is None and ex_off.health_window == 0
        assert ex_on.health_ring is not None
        assert len(ex_off.step_args(1)) == 3
        assert len(ex_on.step_args(1)) == 4
        for a, b in zip(off, on):
            for f in ("vx", "vy", "vz", "p"):
                np.testing.assert_array_equal(a.state[f], b.state[f])


# ---------------------------------------------------------------------------
# telemetry resolution
# ---------------------------------------------------------------------------
class TestResolve:
    def test_specs(self):
        assert obs.resolve(None) is obs.NULL
        assert obs.resolve(False) is obs.NULL
        assert obs.resolve(True).enabled
        tel = obs.telemetry()
        assert obs.resolve(tel) is tel
        assert obs.resolve({"heartbeat_interval_s": 2.0}).config \
            .heartbeat_interval_s == 2.0
        # the disabled handle's section is still a profiler span
        assert isinstance(obs.NULL.section("farm.admit"),
                          jax.profiler.TraceAnnotation)
        assert obs.resolve(obs.TelemetryConfig(enabled=False)) is obs.NULL
        with pytest.raises(TypeError):
            obs.resolve(42)


# ---------------------------------------------------------------------------
# profiler spans and stage scopes
# ---------------------------------------------------------------------------
STAGES = ("update_velocity", "divergence", "jacobi", "project",
          "exchange_pad")
DYNAMIC = ("vx", "vy", "vz", "p")   # the fields the step writes


def _farm_run(telemetry):
    """Two waves through a 2-slot farm via ``SimulationService.run``, and
    two serial steps through ``Runtime.prepare``."""
    rt = api.runtime(n=N, n_slots=2, telemetry=telemetry, **KW)
    for re_ in (70.0, 150.0, 300.0):
        rt.submit("cavity", re=re_, steps=4)
    (svc,) = rt.services()
    while svc.farm.table.n_queued or svc.farm.table.n_active:
        svc.run(4)
    pr = rt.prepare("cavity")
    jax.block_until_ready(pr.step(pr.step(pr.state)))
    return rt, svc


class TestSpans:
    @pytest.mark.parametrize("telemetry", [False, True])
    def test_every_emitted_span_is_published(self, monkeypatch, telemetry):
        seen = []
        real = obs.span

        def record(name, **counts):
            seen.append((name, counts))
            return real(name, **counts)

        monkeypatch.setattr(obs, "span", record)
        _, svc = _farm_run(telemetry)
        names = {n for n, _ in seen}
        assert names <= set(obs.SPANS), names - set(obs.SPANS)
        assert {"service.run", "farm.admit", "farm.step_chunk",
                "farm.harvest", "ensemble.write_slots", "ensemble.read_slots",
                "runtime.step"} <= names
        assert all(n.startswith(("service.", "farm.", "ensemble.",
                                 "runtime.", "schedule."))
                   for n in obs.SPANS)
        fields = svc.farm.exec.state
        assert len(fields) == 7   # vx, vy, vz, p and three masks
        member = sum(fields[f][0].size * fields[f].dtype.itemsize
                     for f in DYNAMIC)
        # two rounds through two slots: both members, then the third;
        # one copy per field the step writes, whatever the round's size
        reads = [c for n, c in seen if n == "ensemble.read_slots"]
        assert reads == [{"members": 2, "transfers": 4, "bytes": 2 * member},
                         {"members": 1, "transfers": 4, "bytes": member}]
        writes = [c for n, c in seen if n == "ensemble.write_slots"]
        assert writes == [{"members": 2, "transfers": 0, "bytes": 0},
                          {"members": 1, "transfers": 0, "bytes": 0}]

    @pytest.mark.parametrize("members", [1, 3, 4])
    def test_read_slots_copies_each_dynamic_field_once(self, monkeypatch,
                                                       members):
        seen = []
        real = obs.span
        monkeypatch.setattr(obs, "span", lambda name, **counts: (
            seen.append((name, counts)), real(name, **counts))[1])
        svc = SimulationService(cavity.config(N, **KW), n_slots=4)
        for i in range(members):
            svc.submit(cavity.sim_request(N, re=100.0 + 50 * i, steps=3,
                                          **KW))
        svc.run(3)
        reads = [c for n, c in seen if n == "ensemble.read_slots"]
        assert [(c["members"], c["transfers"]) for c in reads] == \
            [(members, len(DYNAMIC))]
        assert all(set(svc.result(sid).state) == set(svc.farm.exec.state)
                   for sid in range(members))

    def test_admission_reads_nothing_back_per_member(self, monkeypatch):
        """A round of fresh admissions installs host scalars: no device
        value is converted to a host one (the per-slot scalars used to be
        device scalars read back one by one)."""
        from repro.cfd.ns3d import params_from_config
        from repro.sim import ensemble

        reads = []

        class CountingNumpy:
            """``numpy``, counting its host conversions of device arrays."""

            def __getattr__(self, name):
                fn = getattr(np, name)
                if name not in ("asarray", "array", "stack", "float32"):
                    return fn

                def counted(*args, **kw):
                    if any(isinstance(x, jax.Array)
                           for x in jax.tree.leaves((args, kw))):
                        reads.append(name)
                    return fn(*args, **kw)
                return counted

        svc = SimulationService(cavity.config(N, **KW), n_slots=4)
        for re_ in (70.0, 150.0, 300.0, 450.0):
            svc.submit(cavity.sim_request(N, re=re_, steps=2, **KW))
        monkeypatch.setattr(ensemble, "np", CountingNumpy())
        svc.farm._admit()
        assert svc.farm.table.n_active == 4
        assert reads == []
        # the count sees a device scalar handed to the executor
        svc.farm.exec.write_slot(0, params_from_config(svc.farm.base_config))
        assert len(reads) == len(params_from_config(svc.farm.base_config))

    def test_span_carries_its_stats_into_the_trace(self, tmp_path):
        jax.profiler.start_trace(str(tmp_path))
        with obs.NULL.section("ensemble.read_slot", transfers=7, bytes=96):
            with obs.telemetry().section("farm.harvest", members=3):
                pass
        jax.profiler.stop_trace()
        (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                            recursive=True)
        got = {e.name: dict(e.stats)
               for plane in jax.profiler.ProfileData.from_file(path).planes
               for line in plane.lines for e in line.events
               if e.name in ("ensemble.read_slot", "farm.harvest")}
        assert got == {"ensemble.read_slot": {"transfers": 7, "bytes": 96},
                       "farm.harvest": {"members": 3}}

    def test_telemetry_off_farm_never_waits_from_obs(self, monkeypatch):
        import sys

        callers = []
        real = jax.block_until_ready

        def counting(x):
            callers.append(sys._getframe(1).f_globals["__name__"])
            return real(x)

        def from_obs():
            return sum(name.startswith("repro.obs") for name in callers)

        monkeypatch.setattr(jax, "block_until_ready", counting)
        _farm_run(False)
        assert from_obs() == 0
        _farm_run(True)
        assert from_obs() > 0      # the counter sees the enabled fences

    @pytest.mark.parametrize("where", ["serial", "farm"])
    def test_step_ops_carry_the_stage_scopes(self, where):
        rt = api.runtime(n=N, n_slots=2, **KW)
        if where == "serial":
            pr = rt.prepare("cavity")
            lowered = jax.jit(pr.step).lower(pr.state)
        else:
            rt.submit("cavity", re=100.0, steps=1)
            (svc,) = rt.services()
            ex = svc.farm.exec
            lowered = ex._run_k.lower(*ex.step_args(1))
        paths = set(re.findall(r'op_name="([^"]*)"',
                               lowered.compile().as_text()))
        for stage in STAGES:
            pat = re.compile(rf"(^|[/(]){stage}([)/]|$)")
            assert any(pat.search(p) for p in paths), stage
        if where == "farm":
            assert any("vmap(jacobi)" in p for p in paths)
