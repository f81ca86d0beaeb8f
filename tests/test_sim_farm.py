"""Simulation farm: batched ensembles must reproduce serial runs exactly,
slots must recycle through queued work, and the compile cache must hand out
one executable per static signature."""
import dataclasses

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.cfd import cavity, taylor_green
from repro.cfd.ns3d import NavierStokes3D, params_from_config
from repro.core import generate, mol
from repro.kernels import stencil3d
from repro.sim import (
    EnsembleExecutor, SimulationFarm, SimulationService,
    compile_cache_stats, reset_compile_cache, stack_trees,
)
from tests.helpers import run_with_devices

N = 16
KW = dict(jacobi_iters=20)


def serial_reference(re: float, steps: int):
    """The pre-farm workflow: one solver, one GridDriver-jitted step."""
    solver = NavierStokes3D(cavity.config(N, re=re, **KW))
    state = solver.init_state()
    step = solver.make_step()
    for _ in range(steps):
        state = step(state)
    return jax.device_get(state)


FIELDS = ("vx", "vy", "vz", "p")


class TestFarmMatchesSerial:
    # 8 heterogeneous sims through 4 slots: mixed Reynolds numbers AND mixed
    # step counts, so slots reclaim mid-flight and admissions interleave.
    RES = (50.0, 80.0, 100.0, 150.0, 200.0, 250.0, 300.0, 400.0)
    STEPS = (30, 45, 25, 60, 35, 50, 40, 55)

    @pytest.fixture(scope="class")
    def farm_results(self):
        farm = SimulationFarm(cavity.config(N, **KW), n_slots=4)
        sids = {}
        for re, steps in zip(self.RES, self.STEPS):
            sid = farm.submit(cavity.sim_request(N, re=re, steps=steps, **KW))
            sids[sid] = (re, steps)
        results = farm.run_until_drained()
        return farm, sids, results

    def test_all_complete(self, farm_results):
        farm, sids, results = farm_results
        assert set(results) == set(sids)
        for sid, (_, steps) in sids.items():
            assert results[sid].steps_done == steps
            assert results[sid].terminated == "steps"

    def test_bitwise_identical_to_serial(self, farm_results):
        _, sids, results = farm_results
        for sid, (re, steps) in sids.items():
            ref = serial_reference(re, steps)
            for f in FIELDS:
                np.testing.assert_array_equal(
                    ref[f], results[sid].state[f],
                    err_msg=f"sid={sid} re={re} field={f}")

    def test_slot_reclamation_batches_work(self, farm_results):
        farm, sids, _ = farm_results
        # 4 slots served 8 sims: continuous batching must beat one-at-a-time
        # (sum of steps) and a freed slot must have admitted queued work
        # (device steps strictly less than two sequential half-batches of
        # the worst case, and at least the longest single sim).
        total = sum(s for _, s in sids.values())
        assert farm.device_steps < total
        assert farm.device_steps >= max(s for _, s in sids.values())


class TestCompileCache:
    def test_one_compile_per_static_signature(self):
        reset_compile_cache()
        base = cavity.config(N, **KW)
        farm1 = SimulationFarm(base, n_slots=4)
        for re in (70.0, 120.0, 180.0, 220.0, 260.0):
            farm1.submit(cavity.sim_request(N, re=re, steps=5, **KW))
        farm1.run_until_drained()
        assert compile_cache_stats()["misses"] == 1
        # a second farm of the same shape reuses the compiled step
        farm2 = SimulationFarm(base, n_slots=4)
        farm2.submit(cavity.sim_request(N, re=90.0, steps=5, **KW))
        farm2.run_until_drained()
        stats = compile_cache_stats()
        assert stats["misses"] == 1 and stats["hits"] == 1
        # a different slot count is a different executable
        SimulationFarm(base, n_slots=2)
        assert compile_cache_stats()["misses"] == 2

    def test_static_mismatch_rejected(self):
        farm = SimulationFarm(cavity.config(N, **KW), n_slots=2)
        with pytest.raises(ValueError, match="static config"):
            farm.submit(cavity.sim_request(N, re=100.0, steps=5,
                                           jacobi_iters=33))

    def test_double_submit_rejected(self):
        farm = SimulationFarm(cavity.config(N, **KW), n_slots=2)
        req = cavity.sim_request(N, re=100.0, steps=5, **KW)
        farm.submit(req)
        with pytest.raises(ValueError, match="already submitted"):
            farm.submit(req)


class TestService:
    def test_poll_lifecycle_and_eviction(self):
        svc = SimulationService(cavity.config(N, **KW), n_slots=2)
        a = svc.submit(cavity.sim_request(N, re=100.0, steps=40, **KW))
        b = svc.submit(cavity.sim_request(N, re=200.0, steps=40, **KW))
        c = svc.submit(cavity.sim_request(N, re=300.0, steps=10, **KW))
        assert svc.poll(c)["status"] == "queued"
        svc.run(10)
        assert svc.poll(a)["status"] == "running"
        assert svc.evict(a)
        assert svc.poll(a)["status"] == "evicted"
        # the freed slot admits the queued sim on the next step
        svc.run(1)
        assert svc.poll(c)["status"] == "running"
        # an evicted sim resumes at its exact step and matches serial
        ra = svc.result(a)
        assert ra.steps_done == 40
        ref = serial_reference(100.0, 40)
        for f in FIELDS:
            np.testing.assert_array_equal(ref[f], ra.state[f])
        assert svc.result(b).steps_done == 40
        assert svc.poll(c)["status"] == "done"
        with pytest.raises(KeyError):
            svc.poll(10_000)

    def test_eviction_spills_through_checkpointer(self, tmp_path):
        svc = SimulationService(cavity.config(N, **KW), n_slots=1,
                                ckpt_dir=str(tmp_path))
        a = svc.submit(cavity.sim_request(N, re=100.0, steps=30, **KW))
        svc.run(12)
        assert svc.evict(a)
        # state went to disk, not host RAM
        assert svc._evicted[a].state is None
        assert any(p.name.startswith("step_") for p in tmp_path.iterdir())
        ra = svc.result(a)
        ref = serial_reference(100.0, 30)
        for f in FIELDS:
            np.testing.assert_array_equal(ref[f], ra.state[f])

    def test_steady_state_termination(self):
        svc = SimulationService(cavity.config(N, **KW), n_slots=1,
                                check_steady_every=8)
        a = svc.submit(cavity.sim_request(N, re=100.0, steps=5000,
                                          steady_tol=1e-4, **KW))
        ra = svc.result(a)
        assert ra.terminated == "steady"
        assert ra.steps_done < 5000


class TestTaylorGreenEnsemble:
    def test_mixed_viscosity_matches_serial(self):
        base = taylor_green.config(N, nu=0.1)
        farm = SimulationFarm(base, n_slots=3)
        nus = (0.05, 0.1, 0.2)
        sids = {farm.submit(taylor_green.sim_request(N, nu=nu, steps=12)): nu
                for nu in nus}
        results = farm.run_until_drained()
        for sid, nu in sids.items():
            cfg = taylor_green.config(N, nu=nu)
            solver = NavierStokes3D(cfg)
            state = solver.init_state()
            step = solver.make_step()
            for _ in range(12):
                state = step(state)
            ref = jax.device_get(state)
            for f in FIELDS:
                np.testing.assert_array_equal(ref[f], results[sid].state[f])


# the Pallas farm posture: 3DBLOCK tiles through the interpreter (the CPU
# correctness mode of the TPU path), overlap off as BACKENDS resolves it
PKW = dict(jacobi_iters=20, template="3DBLOCK", interpret=True,
           overlap=False)


class TestPallasFarmParity:
    """The farm's Pallas backend: per-slot scalars through the generator's
    scalar table (scalar prefetch on hardware), one compiled 3DBLOCK
    kernel for every slot.

    Contract: a ``pallas-interpret`` farm run matches the
    pallas-interpret *serial* run of the same request to a few float32
    ulps of the O(1) lid velocity (``PALLAS_ATOL``) — slots carry
    heterogeneous nu/dt/lid scalars, so any literal-baking regression
    (slot 0's physics smeared over the batch, or one kernel per scalar
    tuple) moves fields by orders of magnitude more and shows
    immediately — and matches the JNP farm to fp tolerance (separately
    compiled XLA programs contract FMAs differently; the cross-template
    contract was always tolerance-level, as in ``tests/test_kernels.py``).

    Not bitwise: interpret mode lowers the kernel to an XLA loop over its
    grid, and XLA:CPU's fusion emitters generate different arithmetic for
    that loop body when the grid gains a slot axis (with
    ``--xla_cpu_use_fusion_emitters=false`` the two agree bitwise)."""

    # 8 ulps of 1.0 in float32: far below what a wrong per-slot scalar
    # row moves a field, far above the emitters' ~1 ulp per-step drift
    PALLAS_ATOL = 8 * float(np.finfo(np.float32).eps)

    RES = (50.0, 200.0, 400.0)
    STEPS = (12, 8, 15)

    def _serial(self, cfg, steps):
        solver = NavierStokes3D(cfg)
        state = solver.init_state()
        step = solver.make_step()
        for _ in range(steps):
            state = step(state)
        return jax.device_get(state)

    @pytest.fixture(scope="class")
    def cavity_farms(self):
        """The same heterogeneous requests through a pallas-interpret farm
        and a JNP farm (2 slots serving 3 sims: a reclamation happens)."""
        out = {}
        for kw in (PKW, KW):
            farm = SimulationFarm(cavity.config(N, **kw), n_slots=2)
            sids = {farm.submit(cavity.sim_request(N, re=re, steps=st, **kw)):
                    (re, st) for re, st in zip(self.RES, self.STEPS)}
            results = farm.run_until_drained()
            out[kw["template"] if "template" in kw else "JNP"] = (sids, results)
        return out

    def test_cavity_farm_bitwise_vs_pallas_serial(self, cavity_farms):
        sids, results = cavity_farms["3DBLOCK"]
        for sid, (re, st) in sids.items():
            res = results[sid]
            assert res.terminated == "steps", (res.terminated, res.error)
            ref = self._serial(cavity.config(N, re=re, **PKW), st)
            for f in FIELDS:
                np.testing.assert_allclose(
                    res.state[f], ref[f], rtol=0, atol=self.PALLAS_ATOL,
                    err_msg=f"re={re} field={f}")

    def test_cavity_farm_matches_jnp_farm(self, cavity_farms):
        psids, pres = cavity_farms["3DBLOCK"]
        jsids, jres = cavity_farms["JNP"]
        by_req_p = {k: pres[s] for s, k in psids.items()}
        for sid, key in jsids.items():
            for f in FIELDS:
                np.testing.assert_allclose(
                    jres[sid].state[f], by_req_p[key].state[f],
                    rtol=2e-5, atol=1e-6, err_msg=f"req={key} field={f}")

    def test_taylor_green_heterogeneous_nu_and_dt_bitwise(self):
        """Distinct nu AND dt per slot — dt multiplies every kernel's
        update, so a scalar table that indexed the wrong row (or baked
        slot 0's literals) cannot pass."""
        base = taylor_green.config(N, nu=0.1, dt=1e-3, **PKW)
        farm = SimulationFarm(base, n_slots=3)
        runs = ((0.05, 1.0e-3), (0.1, 0.5e-3), (0.2, 0.25e-3))
        sids = {farm.submit(taylor_green.sim_request(
            N, nu=nu, dt=dt, steps=10, **PKW)): (nu, dt)
            for nu, dt in runs}
        results = farm.run_until_drained()
        for sid, (nu, dt) in sids.items():
            res = results[sid]
            assert res.terminated == "steps", (res.terminated, res.error)
            ref = self._serial(taylor_green.config(N, nu=nu, dt=dt, **PKW),
                               10)
            for f in FIELDS:
                np.testing.assert_array_equal(
                    ref[f], res.state[f], err_msg=f"nu={nu} dt={dt} {f}")

    def test_evict_readmit_cycle_bitwise(self):
        svc = SimulationService(cavity.config(N, **PKW), n_slots=2)
        a = svc.submit(cavity.sim_request(N, re=100.0, steps=24, **PKW))
        b = svc.submit(cavity.sim_request(N, re=200.0, steps=24, **PKW))
        c = svc.submit(cavity.sim_request(N, re=300.0, steps=6, **PKW))
        svc.run(6)
        assert svc.evict(a)
        assert svc.poll(a)["status"] == "evicted"
        ra = svc.result(a)            # readmits and runs to completion
        assert ra.steps_done == 24
        ref = self._serial(cavity.config(N, re=100.0, **PKW), 24)
        for f in FIELDS:
            np.testing.assert_allclose(ra.state[f], ref[f], rtol=0,
                                       atol=self.PALLAS_ATOL, err_msg=f)
        assert svc.result(b).steps_done == 24
        assert svc.result(c).steps_done == 6

    def test_one_compile_for_heterogeneous_scalars(self):
        """Scalar values must not fragment the compile cache: five
        Reynolds variants through a pallas farm are ONE executable."""
        reset_compile_cache()
        farm = SimulationFarm(cavity.config(N, **PKW), n_slots=2)
        for re in (70.0, 120.0, 180.0, 220.0, 260.0):
            farm.submit(cavity.sim_request(N, re=re, steps=3, **PKW))
        results = farm.run_until_drained()
        assert all(r.terminated == "steps" for r in results.values())
        stats = compile_cache_stats()
        assert stats["misses"] == 1 and stats["entries"] == 1

    def test_serial_and_farm_share_autotuned_tiles(self):
        """The roofline tile is resolved per (kernel, local interior,
        chip) and memoized: the farm's batched step re-reads the serial
        path's choices (zero extra misses) — the invariant behind the
        bitwise contract above."""
        from repro.core import reset_tile_cache, tile_cache_stats

        reset_compile_cache()
        reset_tile_cache()
        self._serial(cavity.config(N, re=100.0, **PKW), 1)
        after_serial = tile_cache_stats()
        assert after_serial["misses"] > 0          # the tuner really ran
        farm = SimulationFarm(cavity.config(N, **PKW), n_slots=2)
        farm.submit(cavity.sim_request(N, re=150.0, steps=2, **PKW))
        farm.run_until_drained()
        after_farm = tile_cache_stats()
        assert after_farm["misses"] == after_serial["misses"]
        assert after_farm["hits"] > after_serial["hits"]


class TestEnsembleExecutor:
    def test_write_read_clear_slots(self):
        ex = EnsembleExecutor(cavity.config(N, **KW), n_slots=3)
        cfg = cavity.config(N, re=150.0, **KW)
        ex.write_slot(1, params_from_config(cfg))
        assert ex.params["nu"][1] == np.float32(cfg.nu)
        got = ex.read_slot(1)
        assert set(FIELDS) <= set(got)
        ex.clear_slot(1)
        assert ex.params["lid_velocity"][1] == 0.0
        ke = ex.kinetic_energy()
        assert ke.shape == (3,)


def _recording(monkeypatch, farm):
    """Record each round's slot I/O on ``farm``: the slots of every
    ``read_slots`` call and the ``(slot, hosted?)`` pairs of every
    ``write_slots`` call."""
    reads, writes = [], []
    ex = farm.exec
    read, write = ex.read_slots, ex.write_slots

    def read_slots(slots):
        reads.append(list(slots))
        return read(slots)

    def write_slots(admits):
        writes.append([(slot, state is not None)
                       for slot, _, state in admits])
        return write(admits)

    monkeypatch.setattr(ex, "read_slots", read_slots)
    monkeypatch.setattr(ex, "write_slots", write_slots)
    return reads, writes


class TestBatchedSlotIO:
    """A round's admissions are one ``write_slots`` call and its harvests
    one ``read_slots`` call; every member still equals its serial run in
    all seven fields (the masks come from host copies kept at admission).
    """

    RES = (50.0, 80.0, 100.0, 150.0, 200.0, 250.0, 300.0, 400.0)
    STEPS = 12

    @pytest.fixture(scope="class")
    def two_waves(self):
        mp = pytest.MonkeyPatch()
        farm = SimulationFarm(cavity.config(N, **KW), n_slots=4)
        reads, writes = _recording(mp, farm)
        sids = {farm.submit(cavity.sim_request(N, re=re, steps=self.STEPS,
                                               **KW)): re for re in self.RES}
        results = farm.run_until_drained()
        mp.undo()
        return sids, results, reads, writes

    def test_every_slot_finishes_in_one_round(self, two_waves):
        _, _, reads, writes = two_waves
        assert [len(r) for r in reads] == [4, 4]
        assert [len(w) for w in writes] == [4, 4]
        assert not any(hosted for w in writes for _, hosted in w)

    def test_harvested_states_bitwise_equal_serial(self, two_waves):
        sids, results, _, _ = two_waves
        for sid, re in sids.items():
            ref = serial_reference(re, self.STEPS)
            got = results[sid].state
            assert set(got) == set(ref)
            for f in ref:
                np.testing.assert_array_equal(ref[f], got[f],
                                              err_msg=f"re={re} {f}")

    def test_round_mixing_fresh_and_a_readmission_bitwise(self,
                                                          monkeypatch):
        svc = SimulationService(cavity.config(N, **KW), n_slots=4)
        a = svc.submit(cavity.sim_request(N, re=100.0, steps=24, **KW))
        svc.run(6)
        assert svc.evict(a)
        fresh = {svc.submit(cavity.sim_request(N, re=re, steps=10, **KW)):
                 re for re in (150.0, 200.0, 300.0)}
        assert svc.readmit(a)
        reads, writes = _recording(monkeypatch, svc.farm)
        svc.run(10)
        assert writes[0] == [(0, False), (1, False), (2, False), (3, True)]
        assert [len(r) for r in reads] == [3]
        ra = svc.result(a)
        assert ra.steps_done == 24
        ref = serial_reference(100.0, 24)
        for f in ref:
            np.testing.assert_array_equal(ref[f], ra.state[f], err_msg=f)
        for sid, re in fresh.items():
            ref = serial_reference(re, 10)
            for f in ref:
                np.testing.assert_array_equal(
                    ref[f], svc.result(sid).state[f], err_msg=f"{re} {f}")

    def test_misshaped_readmission_fails_alone(self, monkeypatch):
        farm = SimulationFarm(cavity.config(N, **KW), n_slots=3)
        reads, writes = _recording(monkeypatch, farm)
        good = {farm.submit(cavity.sim_request(N, re=re, steps=8, **KW)): re
                for re in (100.0, 200.0)}
        state = farm.exec.state_template()
        state["p"] = np.zeros((N, N, 3), np.float32)
        bad = farm.submit(dataclasses.replace(
            cavity.sim_request(N, re=300.0, steps=8, **KW),
            init_state=state, step0=2))
        good[farm.submit(cavity.sim_request(N, re=400.0, steps=8,
                                            **KW))] = 400.0
        results = farm.run_until_drained()
        assert results[bad].terminated == "failed"
        assert "shape" in results[bad].error
        # the bad request's slot took the next one in the same round
        assert writes == [[(0, False), (1, False), (2, False)]]
        assert [len(r) for r in reads] == [3]
        for sid, re in good.items():
            assert results[sid].terminated == "steps"
            ref = serial_reference(re, 8)
            for f in ref:
                np.testing.assert_array_equal(ref[f], results[sid].state[f],
                                              err_msg=f"re={re} {f}")


    def test_one_shard_farm_bitwise_equal_plain_farm(self):
        """Two rounds of four on a one-shard slots x shards mesh: each
        round's batched write and read keep the plain farm's results."""
        from repro.launch.mesh import make_mesh

        dkw = dict(KW, decomposition=((0, "shard"),))
        mesh = make_mesh((1, 1), ("slot", "shard"))
        out = []
        for farm, kw in ((SimulationFarm(cavity.config(N, **dkw), n_slots=4,
                                         mesh=mesh, slot_axis="slot"), dkw),
                         (SimulationFarm(cavity.config(N, **KW), n_slots=4),
                          KW)):
            sids = [farm.submit(cavity.sim_request(N, re=re, steps=10, **kw))
                    for re in self.RES]
            results = farm.run_until_drained()
            out.append([results[sid].state for sid in sids])
        for sharded, plain in zip(*out):
            assert set(sharded) == set(plain)
            for f in plain:
                np.testing.assert_array_equal(sharded[f], plain[f],
                                              err_msg=f)

class TestDecompositionDegrade:
    """Fast-lane (1-CPU) coverage of the slots × shards plumbing: a mesh
    whose shard axis has extent 1 degrades to the PR-2 slot-parallel fast
    path, and mis-assembled farms fail with accurate errors (regression:
    the executor used to claim decomposition was unsupported on ANY
    mesh)."""

    DKW = dict(jacobi_iters=20, decomposition=((0, "shard"),))

    def _one_shard_farm(self, n_slots=2):
        from repro.launch.mesh import make_mesh

        mesh = make_mesh((1, 1), ("slot", "shard"))
        return SimulationFarm(cavity.config(N, **self.DKW), n_slots=n_slots,
                              mesh=mesh, slot_axis="slot")

    def test_one_shard_mesh_degrades_to_fast_path(self):
        farm = self._one_shard_farm()
        assert farm.exec.decomposition == {}
        assert farm.exec.slot_sharding() is None
        # the solver really runs undecomposed (no halo collectives traced)
        assert farm.exec.solver.config.decomposition == ()
        assert farm.exec.solver.domain.decomposition == {}

    def test_one_shard_mesh_matches_plain_farm_bitwise(self):
        farm = self._one_shard_farm()
        sid = farm.submit(cavity.sim_request(N, re=100.0, steps=10,
                                             **self.DKW))
        res = farm.run_until_drained()
        plain = SimulationFarm(cavity.config(N, **KW), n_slots=2)
        sid2 = plain.submit(cavity.sim_request(N, re=100.0, steps=10, **KW))
        res2 = plain.run_until_drained()
        for f in FIELDS:
            np.testing.assert_array_equal(res[sid].state[f],
                                          res2[sid2].state[f], err_msg=f)

    def test_degraded_step_compiles_without_collectives(self):
        farm = self._one_shard_farm()
        hlo = farm.exec._run_k.lower(
            farm.exec.state, farm.exec._device_params(),
            jnp.int32(1)).compile().as_text()
        assert "collective-permute" not in hlo

    def test_decomposition_without_mesh_raises_accurately(self):
        # the old message claimed decomposition was unsupported outright;
        # the real contract is "bring a mesh that names the axes"
        with pytest.raises(ValueError, match="mesh"):
            EnsembleExecutor(cavity.config(N, **self.DKW), n_slots=2)

    def test_decomposition_missing_mesh_axis_raises(self):
        from repro.launch.mesh import make_mesh

        mesh = make_mesh((1,), ("slot",))
        with pytest.raises(ValueError, match="shard"):
            SimulationFarm(cavity.config(N, **self.DKW), n_slots=2,
                           mesh=mesh, slot_axis="slot")

    def test_invalid_decomposition_fails_even_on_one_shard_mesh(self):
        """Validation runs before the extent-1 degrade filter: a config
        that would raise on a pod raises identically on a laptop."""
        from repro.launch.mesh import make_mesh

        mesh = make_mesh((1, 1), ("slot", "shard"))
        bad_axis = cavity.config(N, jacobi_iters=20,
                                 decomposition=((5, "shard"),))
        with pytest.raises(ValueError, match="array axis 5"):
            SimulationFarm(bad_axis, n_slots=2, mesh=mesh, slot_axis="slot")
        over_slot = cavity.config(N, jacobi_iters=20,
                                  decomposition=((0, "slot"),))
        with pytest.raises(ValueError, match="slot axis"):
            SimulationFarm(over_slot, n_slots=2, mesh=mesh,
                           slot_axis="slot")
        dup = cavity.config(N, jacobi_iters=20,
                            decomposition=((0, "shard"), (0, "shard")))
        with pytest.raises(ValueError, match="more than once"):
            SimulationFarm(dup, n_slots=2, mesh=mesh, slot_axis="slot")

    def test_decomposition_is_part_of_the_static_signature(self):
        farm = self._one_shard_farm()
        with pytest.raises(ValueError, match="static config"):
            farm.submit(cavity.sim_request(N, re=100.0, steps=5, **KW))


class TestBatchedKernelTemplates:
    """The generator-level slot axis: JNP vmap and the batched 3DBLOCK grid."""

    def _arrays(self, nslots, shape, pad, seed=0):
        rng = np.random.RandomState(seed)
        mk = lambda s: jnp.asarray(
            rng.randn(nslots, *[d + 2 * pad for d in s]).astype(np.float32))
        return mk(shape)

    def test_jnp_batched_equals_per_slot(self):
        kern = generate(stencil3d.DESCRIPTORS["JACOBI_PRESSURE"],
                        stencil3d.BODIES["JACOBI_PRESSURE"], template="JNP")
        nslots, shape = 3, (8, 8, 8)
        p = self._arrays(nslots, shape, 1, seed=1)
        rhs = self._arrays(nslots, shape, 0, seed=2)
        out = kern.apply_batched({"p": p, "rhs": rhs}, h=0.1, omega=0.9)
        for s in range(nslots):
            ref = kern({"p": p[s], "rhs": rhs[s]}, h=0.1, omega=0.9)
            np.testing.assert_array_equal(ref["p"], out["p"][s])

    def test_jnp_batched_per_slot_params(self):
        kern = generate(stencil3d.DESCRIPTORS["JACOBI_PRESSURE"],
                        stencil3d.BODIES["JACOBI_PRESSURE"], template="JNP")
        nslots, shape = 3, (8, 8, 8)
        p = self._arrays(nslots, shape, 1, seed=3)
        rhs = self._arrays(nslots, shape, 0, seed=4)
        omegas = jnp.asarray([0.7, 0.9, 1.0], jnp.float32)
        out = kern.apply_batched({"p": p, "rhs": rhs}, h=0.1, omega=omegas,
                                 batched_params=("omega",))
        for s in range(nslots):
            ref = kern({"p": p[s], "rhs": rhs[s]}, h=0.1, omega=omegas[s])
            np.testing.assert_array_equal(ref["p"], out["p"][s])

    def test_pallas_batched_matches_jnp(self):
        desc = stencil3d.DESCRIPTORS["JACOBI_PRESSURE"]
        body = stencil3d.BODIES["JACOBI_PRESSURE"]
        pallas = generate(desc, body, template="3DBLOCK", interpret=True)
        oracle = generate(desc, body, template="JNP")
        nslots, shape = 2, (8, 8, 8)
        p = self._arrays(nslots, shape, 1, seed=5)
        rhs = self._arrays(nslots, shape, 0, seed=6)
        got = pallas.apply_batched({"p": p, "rhs": rhs}, h=0.1, omega=1.0)
        want = oracle.apply_batched({"p": p, "rhs": rhs}, h=0.1, omega=1.0)
        np.testing.assert_allclose(np.asarray(got["p"]),
                                   np.asarray(want["p"]), atol=1e-6)

    def test_pallas_batched_per_slot_params_bitwise(self):
        """Per-slot scalars through the 3DBLOCK scalar table (the path the
        farm's vmapped step rides): each slot's row must reproduce the
        serial operand-table call bit-for-bit."""
        desc = stencil3d.DESCRIPTORS["JACOBI_PRESSURE"]
        pallas = generate(desc, stencil3d.BODIES["JACOBI_PRESSURE"],
                          template="3DBLOCK", interpret=True)
        nslots, shape = 3, (8, 8, 8)
        p = self._arrays(nslots, shape, 1, seed=7)
        rhs = self._arrays(nslots, shape, 0, seed=8)
        omegas = jnp.asarray([0.7, 0.9, 1.1], jnp.float32)
        out = pallas.apply_batched({"p": p, "rhs": rhs}, h=0.1, omega=omegas,
                                   batched_params=("omega",))
        for s in range(nslots):
            ref = pallas({"p": p[s], "rhs": rhs[s]}, h=0.1, omega=omegas[s])
            np.testing.assert_array_equal(np.asarray(ref["p"]),
                                          np.asarray(out["p"][s]))

    def test_pallas_vmap_dispatches_to_batched_grid(self):
        """jax.vmap of the kernel call (exactly what make_ensemble_step
        does to the solver step) hits the custom_vmap rule and matches
        apply_batched bitwise — under jit, with traced scalars."""
        desc = stencil3d.DESCRIPTORS["JACOBI_PRESSURE"]
        pallas = generate(desc, stencil3d.BODIES["JACOBI_PRESSURE"],
                          template="3DBLOCK", interpret=True)
        nslots, shape = 3, (8, 8, 8)
        p = self._arrays(nslots, shape, 1, seed=9)
        rhs = self._arrays(nslots, shape, 0, seed=10)
        omegas = jnp.asarray([0.7, 0.9, 1.1], jnp.float32)

        @jax.jit
        def farm_like(ps, rs, oms):
            return jax.vmap(
                lambda p1, r1, om: pallas({"p": p1, "rhs": r1},
                                          h=0.1, omega=om)["p"])(ps, rs, oms)

        want = pallas.apply_batched({"p": p, "rhs": rhs}, h=0.1,
                                    omega=omegas, batched_params=("omega",))
        np.testing.assert_array_equal(np.asarray(farm_like(p, rhs, omegas)),
                                      np.asarray(want["p"]))

    def test_pallas_batched_non_array_per_slot_param_rejected(self):
        desc = stencil3d.DESCRIPTORS["JACOBI_PRESSURE"]
        pallas = generate(desc, stencil3d.BODIES["JACOBI_PRESSURE"],
                          template="3DBLOCK", interpret=True)
        with pytest.raises(ValueError, match="array-valued"):
            pallas.apply_batched({"p": jnp.zeros((2, 10, 10, 10)),
                                  "rhs": jnp.zeros((2, 8, 8, 8))},
                                 h=0.1, omega=0.9,
                                 batched_params=("omega",))


class TestBatchedMoL:
    def test_batched_integrators_match_serial(self):
        def rhs(y, t):
            return {"u": -0.5 * y["u"] + jnp.sin(t)}

        ys = [{"u": jnp.full((4,), v, jnp.float32)} for v in (1.0, 2.0, 3.0)]
        ts = jnp.asarray([0.0, 0.1, 0.2], jnp.float32)
        dts = jnp.asarray([0.01, 0.02, 0.005], jnp.float32)
        stacked = stack_trees(ys)
        for name, integ in mol.INTEGRATORS.items():
            batched = mol.BATCHED_INTEGRATORS[name]
            out = jax.jit(lambda y, t, dt: batched(rhs, y, t, dt))(
                stacked, ts, dts)
            for s in range(3):
                ref = integ(rhs, ys[s], ts[s], dts[s])
                np.testing.assert_allclose(np.asarray(ref["u"]),
                                           np.asarray(out["u"][s]),
                                           rtol=1e-6)


@pytest.mark.multidevice
class TestDecomposedFarm:
    """Slots × shards: per-slot grid decomposition composed with slot
    parallelism on a 2-axis ("slot", "shard") farm mesh.

    The correctness contract: a decomposed farm slot is *bitwise* the
    serial ``GridDriver`` run of the same decomposition (the pre-farm
    workflow on a shard-only mesh) — the farm's vmap, chunked ``fori_loop``
    stepping, slot reclamation, and eviction add no numerics on top of the
    decomposed step.  Against the *undecomposed* serial run the match is
    tolerance-level only: ``_global_mean``'s pmean reduces in shard order.
    """

    def test_cavity_slot_shard_farm_bitwise_vs_serial(self):
        script = """
import jax, numpy as np
from repro.cfd import cavity
from repro.cfd.ns3d import NavierStokes3D
from repro.launch.mesh import make_mesh
from repro.sim import SimulationFarm

N = 16
KW = dict(jacobi_iters=20, decomposition=((0, "shard"),))
RES = (50.0, 100.0, 200.0, 400.0, 80.0, 300.0)
STEPS = (20, 30, 25, 35, 30, 20)

def serial(re, steps):
    solver = NavierStokes3D(cavity.config(N, re=re, **KW),
                            make_mesh((4,), ("shard",)))
    state = solver.init_state()
    step = solver.make_step()
    for _ in range(steps):
        state = step(state)
    return jax.device_get(state)

mesh = make_mesh((2, 4), ("slot", "shard"))
farm = SimulationFarm(cavity.config(N, **KW), n_slots=4, mesh=mesh,
                      slot_axis="slot")
assert farm.exec.decomposition == {0: "shard"}
sids = {farm.submit(cavity.sim_request(N, re=re, steps=steps, **KW)):
        (re, steps) for re, steps in zip(RES, STEPS)}
results = farm.run_until_drained()
assert set(results) == set(sids)
for sid, (re, steps) in sids.items():
    assert results[sid].steps_done == steps
    ref = serial(re, steps)
    for f in ("vx", "vy", "vz", "p"):
        np.testing.assert_array_equal(ref[f], results[sid].state[f],
                                      err_msg=f"sid={sid} re={re} {f}")

# the ghost zones really cross devices: the compiled ensemble step must
# contain collective-permutes
import jax.numpy as jnp
hlo = farm.exec._run_k.lower(
    farm.exec.state, farm.exec._device_params(),
    jnp.int32(1)).compile().as_text()
assert "collective-permute" in hlo, "expected ppermute in decomposed step"

# vs the UNdecomposed serial run the physics agree to fp tolerance
solver0 = NavierStokes3D(cavity.config(N, re=RES[0], jacobi_iters=20))
s0 = solver0.init_state()
st0 = solver0.make_step()
for _ in range(STEPS[0]):
    s0 = st0(s0)
first = min(sids, key=lambda s: s)
for f in ("vx", "vy", "vz", "p"):
    d = float(np.abs(np.asarray(s0[f]) - results[first].state[f]).max())
    assert d < 1e-5, (f, d)
print("DECOMPOSED FARM OK")
"""
        out = run_with_devices(script, n_devices=8, timeout=540)
        assert "DECOMPOSED FARM OK" in out

    def test_taylor_green_slot_shard_farm_bitwise_vs_serial(self):
        script = """
import jax, numpy as np
from repro.cfd import taylor_green
from repro.cfd.ns3d import NavierStokes3D
from repro.launch.mesh import make_mesh
from repro.sim import SimulationFarm

N = 16
KW = dict(decomposition=((0, "shard"),))
NUS, STEPS = (0.05, 0.1, 0.2), (12, 16, 10)

mesh = make_mesh((2, 4), ("slot", "shard"))
farm = SimulationFarm(taylor_green.config(N, nu=0.1, **KW), n_slots=2,
                      mesh=mesh, slot_axis="slot")
sids = {farm.submit(taylor_green.sim_request(N, nu=nu, steps=s, **KW)):
        (nu, s) for nu, s in zip(NUS, STEPS)}
results = farm.run_until_drained()
mesh1 = make_mesh((4,), ("shard",))
for sid, (nu, steps) in sids.items():
    solver = NavierStokes3D(taylor_green.config(N, nu=nu, **KW), mesh1)
    state = solver.init_state()
    step = solver.make_step()
    for _ in range(steps):
        state = step(state)
    for f in ("vx", "vy", "vz", "p"):
        np.testing.assert_array_equal(np.asarray(state[f]),
                                      results[sid].state[f],
                                      err_msg=f"nu={nu} {f}")
print("DECOMPOSED TG OK")
"""
        out = run_with_devices(script, n_devices=8, timeout=540)
        assert "DECOMPOSED TG OK" in out

    def test_evict_readmit_cycle_stays_bitwise(self):
        """Eviction gathers the decomposed fields, spills them through the
        checkpointer, and readmission scatters them back to the shard
        layout — the resumed run must still equal the uninterrupted serial
        decomposed reference bitwise."""
        script = """
import tempfile
import jax, numpy as np
from repro.cfd import cavity
from repro.cfd.ns3d import NavierStokes3D
from repro.launch.mesh import make_mesh
from repro.sim import SimulationService

N = 16
KW = dict(jacobi_iters=20, decomposition=((0, "shard"),))

def serial(re, steps):
    solver = NavierStokes3D(cavity.config(N, re=re, **KW),
                            make_mesh((4,), ("shard",)))
    state = solver.init_state()
    step = solver.make_step()
    for _ in range(steps):
        state = step(state)
    return jax.device_get(state)

mesh = make_mesh((2, 4), ("slot", "shard"))
with tempfile.TemporaryDirectory() as d:
    svc = SimulationService(cavity.config(N, **KW), n_slots=2, mesh=mesh,
                            slot_axis="slot", ckpt_dir=d)
    a = svc.submit(cavity.sim_request(N, re=100.0, steps=40, **KW))
    b = svc.submit(cavity.sim_request(N, re=200.0, steps=40, **KW))
    svc.run(10)
    assert svc.evict(a)
    assert svc._evicted[a].state is None     # spilled to disk, not host RAM
    ra = svc.result(a)                       # readmits + runs to completion
    assert ra.steps_done == 40
    ref = serial(100.0, 40)
    for f in ("vx", "vy", "vz", "p"):
        np.testing.assert_array_equal(ref[f], ra.state[f], err_msg=f)
    rb = svc.result(b)
    ref_b = serial(200.0, 40)
    for f in ("vx", "vy", "vz", "p"):
        np.testing.assert_array_equal(ref_b[f], rb.state[f], err_msg=f)
print("EVICT/READMIT OK")
"""
        out = run_with_devices(script, n_devices=8, timeout=540)
        assert "EVICT/READMIT OK" in out

    def test_two_axis_decomposition(self):
        """x over "sx" AND y over "sy" (2-D grid decomposition per slot,
        slot axis on top: a 3-axis farm mesh)."""
        script = """
import jax, numpy as np
from repro.cfd import taylor_green
from repro.cfd.ns3d import NavierStokes3D
from repro.launch.mesh import make_mesh
from repro.sim import SimulationFarm

N = 16
KW = dict(decomposition=((0, "sx"), (1, "sy")))
mesh = make_mesh((2, 2, 2), ("slot", "sx", "sy"))
farm = SimulationFarm(taylor_green.config(N, nu=0.1, **KW), n_slots=2,
                      mesh=mesh, slot_axis="slot")
assert farm.exec.decomposition == {0: "sx", 1: "sy"}
sid = farm.submit(taylor_green.sim_request(N, nu=0.08, steps=10, **KW))
results = farm.run_until_drained()
solver = NavierStokes3D(taylor_green.config(N, nu=0.08, **KW),
                        make_mesh((2, 2), ("sx", "sy")))
state = solver.init_state()
step = solver.make_step()
for _ in range(10):
    state = step(state)
for f in ("vx", "vy", "vz", "p"):
    np.testing.assert_array_equal(np.asarray(state[f]),
                                  results[sid].state[f], err_msg=f)
print("2D DECOMP OK")
"""
        out = run_with_devices(script, n_devices=8, timeout=540)
        assert "2D DECOMP OK" in out

    def test_pallas_slot_shard_farm_bitwise_vs_serial(self):
        """The full posture the tentpole unlocks: 3DBLOCK Pallas kernels
        (interpret mode), per-slot scalars through the generator's scalar
        table, grid decomposition per slot, slot parallelism on top —
        bitwise the serial decomposed pallas-interpret run."""
        script = """
import jax, numpy as np
from repro.cfd import cavity
from repro.cfd.ns3d import NavierStokes3D
from repro.launch.mesh import make_mesh
from repro.sim import SimulationFarm

N = 16
KW = dict(jacobi_iters=20, template="3DBLOCK", interpret=True,
          overlap=False, decomposition=((0, "shard"),))
RES = (100.0, 250.0, 400.0)
STEPS = (8, 12, 6)

def serial(re, steps):
    solver = NavierStokes3D(cavity.config(N, re=re, **KW),
                            make_mesh((4,), ("shard",)))
    state = solver.init_state()
    step = solver.make_step()
    for _ in range(steps):
        state = step(state)
    return jax.device_get(state)

mesh = make_mesh((2, 4), ("slot", "shard"))
farm = SimulationFarm(cavity.config(N, **KW), n_slots=2, mesh=mesh,
                      slot_axis="slot")
sids = {farm.submit(cavity.sim_request(N, re=re, steps=s, **KW)): (re, s)
        for re, s in zip(RES, STEPS)}
results = farm.run_until_drained()
for sid, (re, steps) in sids.items():
    res = results[sid]
    assert res.terminated == "steps", (res.terminated, res.error)
    ref = serial(re, steps)
    for f in ("vx", "vy", "vz", "p"):
        np.testing.assert_array_equal(ref[f], res.state[f],
                                      err_msg=f"re={re} {f}")
print("PALLAS SLOT-SHARD OK")
"""
        out = run_with_devices(script, n_devices=8, timeout=540)
        assert "PALLAS SLOT-SHARD OK" in out


@pytest.mark.multidevice
class TestMultiDeviceFarm:
    def test_sharded_farm_matches_single_device(self):
        """Slot axis over a data-parallel mesh axis (vmap x shard_map via
        sim.ensemble.slot_spec): the distributed farm must reproduce the
        single-device farm bitwise — slots never interact, so placement
        is pure bookkeeping."""
        from tests.helpers import run_with_devices

        script = """
import dataclasses

import numpy as np
from repro.cfd import cavity
from repro.launch.mesh import make_mesh
from repro.sim import SimulationFarm

N = 16
KW = dict(jacobi_iters=20)
RES = (50.0, 100.0, 200.0, 400.0, 80.0, 300.0)
STEPS = (20, 30, 25, 35, 30, 20)

def run(mesh):
    farm = SimulationFarm(cavity.config(N, **KW), n_slots=4, mesh=mesh)
    for re, steps in zip(RES, STEPS):
        farm.submit(cavity.sim_request(N, re=re, steps=steps, **KW))
    return farm.run_until_drained()

res_a = run(None)
res_b = run(make_mesh((4,), ("data",)))
assert set(res_a) == set(res_b) and len(res_a) == len(RES)
for sid in res_a:
    assert res_a[sid].steps_done == res_b[sid].steps_done
    assert res_a[sid].terminated == res_b[sid].terminated
    for f in ("vx", "vy", "vz", "p"):
        np.testing.assert_array_equal(res_a[sid].state[f],
                                      res_b[sid].state[f])
print("FARM MESH OK")
"""
        out = run_with_devices(script, n_devices=4)
        assert "FARM MESH OK" in out
