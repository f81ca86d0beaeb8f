"""The runtime front door (repro.api) + scenario registry.

The migration contract frozen here: everything the Runtime resolves —
serial driver runs, slot-parallel farms, slots × shards decomposition —
is *bitwise identical* to hand-assembling the legacy constructor stack.
Plus: registry round-trips, schedule-bin ordering laws (hypothesis),
residual-based convergence, priority admission, per-sim failure
surfacing, and import hygiene for examples/ and benchmarks/.
"""
import dataclasses
import os

import numpy as np
import pytest
import jax
from hypothesis import given, settings, strategies as st

from repro import api
from repro.cfd import cavity, taylor_green
from repro.cfd.ns3d import NavierStokes3D
from repro.core.schedule import BINS, Schedule, ScheduleError
from repro.sim import SimulationFarm, SimulationService
from tests.helpers import imported_modules, run_with_devices

N = 16
KW = dict(jacobi_iters=20)
FIELDS = ("vx", "vy", "vz", "p")


def serial_reference(scenario: str, steps: int, **kw):
    """The pre-api workflow: one solver, one GridDriver-jitted step."""
    mod = {"cavity": cavity, "taylor_green": taylor_green}[scenario]
    solver = NavierStokes3D(mod.config(N, **kw, **KW))
    state = solver.init_state()
    step = solver.make_step()
    for _ in range(steps):
        state = step(state)
    return jax.device_get(state)


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------
class TestRegistry:
    def test_builtins_registered(self):
        names = api.scenario_names()
        for want in ("cavity", "taylor_green", "kelvin_helmholtz"):
            assert want in names

    def test_round_trip(self):
        sc = api.get_scenario("cavity")
        assert sc.name == "cavity"
        assert api.get_scenario(sc) is sc          # Scenario passes through
        assert "re" in sc.params

    def test_unknown_scenario_error_names_the_registry(self):
        with pytest.raises(api.UnknownScenarioError, match="cavity"):
            api.get_scenario("no_such_scenario")
        rt = api.runtime(n=N)
        with pytest.raises(api.UnknownScenarioError):
            rt.run("no_such_scenario", steps=1)

    def test_third_party_registration(self):
        """Registering a custom scenario through the public decorator makes
        it resolvable by name through the same front door."""
        base = api.get_scenario("taylor_green")
        custom = dataclasses.replace(base, name="tg_custom_test",
                                     description="third-party variant")
        try:
            api.register_scenario(custom)
            rt = api.runtime(n=N, **KW)
            res = rt.run("tg_custom_test", steps=3, nu=0.1)
            ref = serial_reference("taylor_green", 3, nu=0.1)
            for f in FIELDS:
                np.testing.assert_array_equal(ref[f], res.state[f])
        finally:
            api.unregister_scenario("tg_custom_test")
        with pytest.raises(api.UnknownScenarioError):
            api.get_scenario("tg_custom_test")

    def test_duplicate_registration_rejected(self):
        with pytest.raises(ValueError, match="already registered"):
            api.register_scenario(api.get_scenario("cavity"))

    def test_unknown_backend_rejected(self):
        with pytest.raises(ValueError, match="backend"):
            api.runtime(n=N, backend="cuda")


# ---------------------------------------------------------------------------
# schedule-bin ordering (hypothesis property)
# ---------------------------------------------------------------------------
def _entries_strategy():
    """Up to 7 named entries with before/after constraints drawn only
    against *earlier* entries, each pointing the way a hidden random
    total order (``rank``) says — a DAG by construction."""
    def build(n, edges, rank):
        out = []
        for i in range(n):
            js = [j for j in range(i) if (i, j) in edges]
            befores = tuple(f"e{j}" for j in js if rank[i] < rank[j])
            afters = tuple(f"e{j}" for j in js if rank[i] > rank[j])
            out.append((f"e{i}", befores, afters))
        return out

    edge = st.tuples(st.integers(0, 6), st.integers(0, 6))
    return st.builds(build, st.integers(1, 7), st.sets(edge, max_size=8),
                     st.permutations(range(7)))


class TestScheduleOrdering:
    @settings(max_examples=40, deadline=None)
    @given(entries=_entries_strategy(), bin=st.sampled_from(
        ["INITIAL", "EVOLVE", "ANALYSIS"]))
    def test_order_respects_constraints(self, entries, bin):
        s = Schedule()
        for name, befores, afters in entries:
            s.register(bin, name, before=befores, after=afters)(
                lambda st_, name=name: st_ + [name])
        order = s.compile_bin(bin)([])
        assert sorted(order) == sorted(n for n, _, _ in entries)
        pos = {n: i for i, n in enumerate(order)}
        for name, befores, afters in entries:
            for b in befores:
                assert pos[name] < pos[b], (name, "before", b, order)
            for a in afters:
                assert pos[a] < pos[name], (name, "after", a, order)

    def test_evolve_aliases_evol(self):
        s = Schedule()
        s.register("EVOLVE", "x")(lambda st_: st_ + ["x"])
        assert s.names("EVOL") == ["x"] == s.names("EVOLVE")

    def test_unknown_bin_still_rejected(self):
        with pytest.raises(ScheduleError, match="unknown schedule bin"):
            Schedule().register("EVOLVED", "x")(lambda st_: st_)

    def test_scenario_bins_are_wired(self):
        sc = api.get_scenario("kelvin_helmholtz")
        solver = NavierStokes3D(sc.config(N))
        sched = sc.schedule(solver)
        assert sched.names("INITIAL") == ["allocate_fields",
                                          "ic_kelvin_helmholtz"]
        assert sched.names("EVOLVE") == ["ns3d_step"]
        assert set(sched.names("ANALYSIS")) == {"amplitude",
                                                "kinetic_energy"}
        assert set(BINS) >= {"INITIAL", "EVOL", "ANALYSIS"}


# ---------------------------------------------------------------------------
# bitwise equivalence: Runtime vs legacy constructors (serial, fast lane)
# ---------------------------------------------------------------------------
class TestBitwiseEquivalence:
    @pytest.mark.parametrize("scenario,params", [
        ("cavity", dict(re=120.0)),
        ("taylor_green", dict(nu=0.07)),
    ])
    def test_run_matches_legacy_serial(self, scenario, params):
        rt = api.runtime(n=N, **KW)
        res = rt.run(scenario, steps=20, **params)
        ref = serial_reference(scenario, 20, **params)
        for f in FIELDS:
            np.testing.assert_array_equal(ref[f], res.state[f], err_msg=f)
        assert res.terminated == "steps" and res.steps_done == 20

    def test_submit_matches_legacy_farm(self):
        """Runtime.submit/drain vs a hand-built SimulationFarm, mixed
        Reynolds numbers AND step counts (slots reclaim mid-flight)."""
        jobs = ((80.0, 10), (150.0, 16), (220.0, 12), (300.0, 18))
        rt = api.runtime(n=N, n_slots=2, **KW)
        sids = [rt.submit("cavity", steps=s, re=re) for re, s in jobs]
        results = rt.drain()
        legacy = SimulationFarm(cavity.config(N, template="JNP", **KW),
                                n_slots=2)
        lsids = [legacy.submit(cavity.sim_request(
            N, re=re, steps=s, template="JNP", **KW)) for re, s in jobs]
        lres = legacy.run_until_drained()
        for s_new, s_old in zip(sids, lsids):
            assert results[s_new].steps_done == lres[s_old].steps_done
            for f in FIELDS:
                np.testing.assert_array_equal(
                    results[s_new].state[f], lres[s_old].state[f],
                    err_msg=f)

    def test_prepare_exposes_the_same_step(self):
        """PreparedRun.step is the legacy jitted step: stepping it by hand
        reproduces Runtime.run bitwise (benchmarks rely on this)."""
        rt = api.runtime(n=N, **KW)
        pr = rt.prepare("cavity", re=90.0)
        st = pr.state
        for _ in range(8):
            st = pr.step(st)
        res = rt.run("cavity", steps=8, re=90.0)
        for f in FIELDS:
            np.testing.assert_array_equal(np.asarray(st[f]), res.state[f])

    def test_kh_scenario_farm_matches_serial_run(self):
        """A scenario with a registered IC: the farm path (init_state
        shipped in the request) equals the serial path bitwise."""
        rt = api.runtime(n=N, n_slots=2, jacobi_iters=30)
        res = rt.run("kelvin_helmholtz", steps=10, nu=0.004)
        sid = rt.submit("kelvin_helmholtz", steps=10, nu=0.004)
        far = rt.result(sid)
        for f in FIELDS:
            np.testing.assert_array_equal(res.state[f], far.state[f],
                                          err_msg=f)
        assert res.diagnostics["amplitude"] > 0.0


# ---------------------------------------------------------------------------
# convergence: residual norms replace the KE-drift heuristic
# ---------------------------------------------------------------------------
class TestResidualConvergence:
    def test_serial_and_farm_agree_on_termination_step(self):
        rt_serial = api.runtime(n=N, check_every=8, **KW)
        r1 = rt_serial.run("cavity", steps=5000, re=100.0,
                           residual_tol=1e-3)
        assert r1.terminated == "residual" and r1.steps_done < 5000
        rt_farm = api.runtime(n=N, n_slots=1, check_every=8, **KW)
        sid = rt_farm.submit("cavity", steps=5000, re=100.0,
                             residual_tol=1e-3)
        r2 = rt_farm.result(sid)
        assert r2.terminated == "residual"
        assert r2.steps_done == r1.steps_done
        for f in FIELDS:
            np.testing.assert_array_equal(r1.state[f], r2.state[f])

    def test_residual_checks_do_not_perturb_the_state_path(self):
        """A run with residual watching that terminates on steps is
        bitwise the run without it (snapshots only, no numerics)."""
        rt = api.runtime(n=N, check_every=8, **KW)
        plain = rt.run("cavity", steps=24, re=100.0)
        watched = rt.run("cavity", steps=24, re=100.0, residual_tol=1e-30)
        assert watched.terminated == "steps"
        for f in FIELDS:
            np.testing.assert_array_equal(plain.state[f], watched.state[f])
        farm = SimulationFarm(cavity.config(N, **KW), n_slots=1,
                              check_steady_every=8)
        sid = farm.submit(cavity.sim_request(N, re=100.0, steps=24,
                                             residual_tol=1e-30, **KW))
        res = farm.run_until_drained()[sid]
        assert res.terminated == "steps"
        ref = serial_reference("cavity", 24, re=100.0)
        for f in FIELDS:
            np.testing.assert_array_equal(ref[f], res.state[f])

    def test_legacy_ke_heuristic_still_available(self):
        rt = api.runtime(n=N, check_every=8, **KW)
        r = rt.run("cavity", steps=5000, re=100.0, steady_tol=1e-4)
        assert r.terminated == "steady" and r.steps_done < 5000


# ---------------------------------------------------------------------------
# priority admission
# ---------------------------------------------------------------------------
class TestPriorityAdmission:
    def test_two_level_pop_fifo_within_level(self):
        farm = SimulationFarm(cavity.config(N, **KW), n_slots=1)
        reqs = [cavity.sim_request(N, re=re, steps=2, priority=p, **KW)
                for re, p in ((50.0, 0), (60.0, 0), (70.0, 1), (80.0, 1))]
        sids = [farm.submit(r) for r in reqs]
        finish_order = []
        while len(farm.results) < 4:
            farm.step()
            for sid in farm.results:
                if sid not in finish_order:
                    finish_order.append(sid)
        # high-priority pair first (FIFO within level), then the level-0
        # pair in submission order
        assert finish_order == [sids[2], sids[3], sids[0], sids[1]]

    def test_runtime_priority_passthrough(self):
        rt = api.runtime(n=N, n_slots=1, **KW)
        lo = rt.submit("cavity", steps=2, re=50.0)
        hi = rt.submit("cavity", steps=2, re=60.0, priority=5)
        svc = rt.services()[0]
        svc.farm.step()          # admits exactly one request
        assert rt.poll(hi)["status"] in ("running", "done")
        assert rt.poll(lo)["status"] == "queued"
        rt.drain()


# ---------------------------------------------------------------------------
# failure surfacing (the drain bugfix)
# ---------------------------------------------------------------------------
class TestFailureSurfacing:
    def test_unbuildable_signature_resolves_to_failed_result(self):
        """A decomposition with no mesh to satisfy it fails that sid —
        poll/result/drain all surface it; nothing blocks."""
        rt = api.runtime(n=N, decomposition=((0, "shard"),), **KW)
        sid = rt.submit("cavity", steps=5, re=100.0)
        assert rt.poll(sid)["status"] == "failed"
        assert "decomposition" in rt.poll(sid)["error"]
        out = rt.drain()
        assert out[sid].terminated == "failed"
        with pytest.raises(RuntimeError, match="failed"):
            rt.result(sid)

    def test_admission_failure_is_per_sim_and_drain_completes(self):
        """A request whose slot admission raises (mis-shaped readmission
        state) resolves to a failed result; healthy sims in the same farm
        drain normally — drain never wedges on the broken one."""
        svc = SimulationService(cavity.config(N, **KW), n_slots=1)
        good = svc.submit(cavity.sim_request(N, re=100.0, steps=5, **KW))
        bad_req = cavity.sim_request(N, re=200.0, steps=5, **KW)
        bad_req.init_state = {"vx": np.zeros((3, 3, 3), np.float32)}
        bad = svc.submit(bad_req)
        out = svc.drain()
        assert out[good].terminated == "steps"
        assert out[bad].terminated == "failed" and out[bad].error
        assert svc.poll(bad)["status"] == "failed"
        with pytest.raises(RuntimeError, match="failed"):
            svc.result(bad)
        # the good result is still bitwise exact after the failure
        ref = serial_reference("cavity", 5, re=100.0)
        for f in FIELDS:
            np.testing.assert_array_equal(ref[f], out[good].state[f])


# ---------------------------------------------------------------------------
# import hygiene: examples/ and benchmarks/ go through repro.api
# ---------------------------------------------------------------------------
FORBIDDEN_MODULES = ("repro.sim.ensemble", "repro.sim.farm",
                     "repro.sim.service", "repro.core.driver")


def test_examples_and_benchmarks_import_through_the_api():
    """The front door is the only supported path into the farm/driver
    internals: examples and benchmarks must not reach around it."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    offenders = []
    for d in ("examples", "benchmarks"):
        for fname in sorted(os.listdir(os.path.join(root, d))):
            if not fname.endswith(".py"):
                continue
            for mod in imported_modules(os.path.join(root, d, fname)):
                if mod in FORBIDDEN_MODULES:
                    offenders.append(f"{d}/{fname} imports {mod}")
    assert not offenders, (
        "examples/benchmarks must go through repro.api, not the "
        f"constructor internals: {offenders}")


# ---------------------------------------------------------------------------
# decomposed equivalence (multidevice lane)
# ---------------------------------------------------------------------------
@pytest.mark.multidevice
class TestRuntimeDecomposed:
    def test_runtime_matches_legacy_across_postures(self):
        """One script, three postures: slot-parallel farm, slots × shards
        farm, and serial decomposed run — each bitwise against its legacy
        constructor stack."""
        script = """
import numpy as np, jax
from repro import api
from repro.cfd import cavity
from repro.cfd.ns3d import NavierStokes3D
from repro.launch.mesh import make_mesh
from repro.sim import SimulationFarm

N, KW = 16, dict(jacobi_iters=20)
DKW = dict(jacobi_iters=20, decomposition=((0, "shard"),), template="JNP")
JOBS = ((50.0, 20), (100.0, 30), (200.0, 25), (400.0, 35))
FIELDS = ("vx", "vy", "vz", "p")

# 1) slot-parallel: Runtime.submit on a ("slot",) mesh vs single-device farm
rt = api.runtime(n=N, n_slots=4, mesh_shape=(4,), mesh_axes=("slot",), **KW)
sids = [rt.submit("cavity", steps=s, re=re) for re, s in JOBS]
res = rt.drain()
legacy = SimulationFarm(cavity.config(N, template="JNP", **KW), n_slots=4)
lsids = [legacy.submit(cavity.sim_request(N, re=re, steps=s,
                                          template="JNP", **KW))
         for re, s in JOBS]
lres = legacy.run_until_drained()
for a, b in zip(sids, lsids):
    for f in FIELDS:
        np.testing.assert_array_equal(res[a].state[f], lres[b].state[f],
                                      err_msg=f"slot {f}")
print("SLOT-PARALLEL OK")

# 2) slots x shards: Runtime.submit vs serial decomposed GridDriver
rt2 = api.runtime(n=N, n_slots=2, mesh_shape=(2, 4),
                  mesh_axes=("slot", "shard"),
                  decomposition=((0, "shard"),), **KW)
sid = rt2.submit("cavity", steps=30, re=100.0)
r2 = rt2.result(sid)
solver = NavierStokes3D(cavity.config(N, re=100.0, **DKW),
                        make_mesh((4,), ("shard",)))
st = solver.init_state(); step = solver.make_step()
for _ in range(30):
    st = step(st)
st = jax.device_get(st)
for f in FIELDS:
    np.testing.assert_array_equal(st[f], r2.state[f], err_msg=f)
print("SLOTS X SHARDS OK")

# 3) serial decomposed: Runtime.run on a ("shard",) mesh
rt3 = api.runtime(n=N, mesh_shape=(4,), mesh_axes=("shard",),
                  decomposition=((0, "shard"),), **KW)
r3 = rt3.run("cavity", steps=30, re=100.0)
for f in FIELDS:
    np.testing.assert_array_equal(st[f], r3.state[f], err_msg=f)
print("SERIAL DECOMPOSED OK")
"""
        out = run_with_devices(script, n_devices=8, timeout=540)
        for tag in ("SLOT-PARALLEL OK", "SLOTS X SHARDS OK",
                    "SERIAL DECOMPOSED OK"):
            assert tag in out

    def test_indivisible_decomposition_fails_per_sim_on_a_healthy_farm(self):
        """The drain bugfix, at its literal repro: an indivisible
        decomposition (18 % 4 != 0) submitted to a runtime whose healthy
        signature keeps serving — the bad sid resolves to failed, the
        good one drains bitwise-intact, drain returns."""
        script = """
import numpy as np
from repro import api

KW = dict(jacobi_iters=20)
rt = api.runtime(n=16, n_slots=2, mesh_shape=(1, 4),
                 mesh_axes=("slot", "shard"),
                 decomposition=((0, "shard"),), **KW)
ok = rt.submit("cavity", steps=10, re=100.0)
bad = rt.submit("cavity", n=18, steps=10, re=100.0)  # 18 % 4 != 0
assert rt.poll(bad)["status"] == "failed", rt.poll(bad)
out = rt.drain()
assert out[ok].terminated == "steps"
assert out[bad].terminated == "failed"
assert "divisible" in out[bad].error, out[bad].error
print("INDIVISIBLE FAILED-SIM OK")
"""
        out = run_with_devices(script, n_devices=8, timeout=540)
        assert "INDIVISIBLE FAILED-SIM OK" in out
