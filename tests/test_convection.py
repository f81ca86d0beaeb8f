"""Rayleigh-Benard convection (``rayleigh_benard``): a temperature field T
with buoyancy, walls in x, y and z periodic.

The step through ``Runtime.prepare`` against the plain float32 reference
of the equations (``benchmarks/chip/reference_convection.py``); the
``ADVECT_DIFFUSE_SCALAR`` descriptor against its ``kernels/ref.py``
oracle; the conductive state as a fixed point; the linear stability
threshold; the Nusselt analysis; a five-field state through the farm,
health, the job codec and the halo counters; and the benchmark cell
``rb-g6-dns`` at a tiny size on the CPU.
"""
import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.cfd import ns3d
from repro.cfd.ns3d import CFDConfig, NavierStokes3D
from repro.jobs.codec import decode_request, encode_request
from repro.kernels import ops, ref
from repro.obs import perf
from repro.sim.ensemble import EnsembleExecutor

CHIP = Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
if str(CHIP) not in sys.path:
    sys.path.append(str(CHIP))

import reference_convection as rc  # noqa: E402

# 8 cells deep, aspect ratio 2: a layer of 8 x 16 x 16 cells, one pair of
# rolls across each periodic axis
N, ASPECT = 16, 2
SHAPE = (N // ASPECT, N, N)


def _prepare(**kw):
    kw.setdefault("aspect", ASPECT)
    return api.runtime(n=N, nz=N).prepare("rayleigh_benard", **kw)


# ---------------------------------------------------------------------------
# the step against the plain reference
# ---------------------------------------------------------------------------
STEPS, SWEEPS = 3, 200
# Three steps of the same arithmetic in float32, apart from the order of
# the pressure mean's sum: the gap stays within a few ulps of each field's
# scale (it reads 0 or about 1e-7 here).  bfloat16's 8-bit mantissa puts
# the control's gap near 1e-2, far past this.
TOLERANCE = 1e-5


def _reference_gaps(dtype):
    """The gaps of the program's three steps against the float32
    reference; for ``dtype`` bfloat16, those of the reference computed in
    bfloat16 in the program's place (the benchmark cell's control)."""
    import reference

    pr = _prepare(ra=1e5, jacobi_iters=SWEEPS)
    c = pr.config
    ic = rc.initial_fields(c.shape, c.h, seed=11, amplitude=0.2)
    nu, kappa = rc.viscosities(1e5, 0.7)
    kw = dict(h=c.h, dt=c.dt, nu=nu, kappa=kappa, sweeps=SWEEPS)
    want = rc.evolve(ic, STEPS, **kw)
    if dtype == jnp.float32:
        got = dict(pr.state, **ic)
        for _ in range(STEPS):
            got = pr.step(got)
    else:
        got = rc.evolve({f: v.astype(dtype) for f, v in ic.items()}, STEPS,
                        **kw)
    got = {f: got[f] for f in rc.FIELDS}
    return reference.device_gap(got, want), rc.temperature_gap(got, want)


def test_step_matches_the_plain_reference():
    state_gap, temperature_gap = _reference_gaps(jnp.float32)
    assert state_gap <= TOLERANCE and temperature_gap <= TOLERANCE, (
        state_gap, temperature_gap)


def test_bfloat16_reference_fails_the_tolerance():
    state_gap, temperature_gap = _reference_gaps(jnp.bfloat16)
    assert state_gap > TOLERANCE and temperature_gap > TOLERANCE, (
        state_gap, temperature_gap)


# ---------------------------------------------------------------------------
# the descriptor kernel
# ---------------------------------------------------------------------------
def _scalar_inputs(shape, seed):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    padded = tuple(n + 2 for n in shape)
    return [jax.random.normal(k, padded, jnp.float32) * s
            for k, s in zip(keys, (1.0, 0.3, 0.3, 0.3))]


@pytest.mark.parametrize("shape", [(8, 8, 8), (4, 8, 16)])
def test_scalar_descriptor_matches_its_oracle(shape):
    T, vx, vy, vz = _scalar_inputs(shape, 5)
    args = dict(dt=0.01, h=0.1, kappa=0.05)
    want = ref.advect_diffuse_scalar(T, vx, vy, vz, **args)
    got = ops.advect_diffuse_scalar(T, vx, vy, vz, template="JNP", **args)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    got = ops.advect_diffuse_scalar(T, vx, vy, vz, template="3DBLOCK",
                                    interpret=True, tile=(4, 4, 8), **args)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_scalar_advection_conserves_the_periodic_total():
    """Flux form: with periodic ghosts and no diffusion, what leaves one
    cell enters its neighbour."""
    T, vx, vy, vz = (jnp.pad(a[1:-1, 1:-1, 1:-1], 1, mode="wrap")
                     for a in _scalar_inputs((8, 8, 8), 6))
    new = ops.advect_diffuse_scalar(T, vx, vy, vz, dt=0.01, h=0.5, kappa=0.0)
    np.testing.assert_allclose(float(new.sum()),
                               float(T[1:-1, 1:-1, 1:-1].sum()),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# physics
# ---------------------------------------------------------------------------
def _conductive(pr):
    """T = 1/2 - x, u = 0, and the discrete hydrostatic p, with
    p[i+1] - p[i] = h (T[i] + T[i+1]) / 2, less its mean."""
    c = pr.config
    nx = c.shape[0]
    x = (np.arange(nx) + 0.5) * c.h
    t = 0.5 - x
    p = np.concatenate([[0.0], np.cumsum(c.h * 0.5 * (t[:-1] + t[1:]))])
    p -= p.mean()
    full = lambda a: jnp.asarray(np.broadcast_to(
        a[:, None, None], c.shape).astype(np.float32))
    zero = jnp.zeros(c.shape, jnp.float32)
    return dict(pr.state, vx=zero, vy=zero, vz=zero, T=full(t), p=full(p))


def test_conductive_state_is_a_fixed_point():
    """Ten steps from the conductive state leave it where it was, to
    float32 rounding: the buoyancy's face forcing is the hydrostatic p's
    gradient exactly, a fixed point of the sweeps, so the projection
    cancels it; T is linear in x, which the plates' ghost rule extends,
    so its Laplacian is 0.  The bounds are a few hundred float32 ulps of
    each field's scale (T 1/2, p 1/8, the face forcing dt/2)."""
    pr = _prepare(ra=1e4, jacobi_iters=SWEEPS)
    start = _conductive(pr)
    state = start
    for _ in range(10):
        state = pr.step(state)
    eps = np.finfo(np.float32).eps
    dt = pr.config.dt
    for f in ("vx", "vy", "vz"):
        assert np.abs(np.asarray(state[f])).max() <= 300 * eps * dt, f
    for f, scale in (("T", 0.5), ("p", 0.125)):
        gap = np.abs(np.asarray(state[f]) - np.asarray(start[f])).max()
        assert gap <= 300 * eps * scale, (f, gap)
    assert pr.analyze(state)["nusselt"]["mean"] == pytest.approx(1.0,
                                                                 abs=1e-5)


def test_nusselt_reads_one_on_the_conductive_state():
    pr = _prepare()
    nu = pr.analyze(_conductive(pr))["nusselt"]
    for plate in ("bottom", "top", "mean"):
        assert nu[plate] == pytest.approx(1.0, abs=1e-5), nu


@pytest.mark.parametrize("ra, grows", [(1000.0, False), (20000.0, True)])
def test_perturbation_follows_the_stability_threshold(ra, grows):
    """Rigid plates go unstable past Ra 1708 (Chandrasekhar 1961): from
    the conductive state with a perturbation of 1e-3, the kinetic energy
    decays at Ra 1,000 and grows at Ra 20,000 (by about 25x from step 40
    to step 160 here, while it falls to a quarter at Ra 1,000)."""
    pr = _prepare(ra=ra, jacobi_iters=SWEEPS, amplitude=1e-3, seed=3)
    state, ke = pr.state, {}
    for i in range(1, 161):
        state = pr.step(state)
        if i in (40, 160):
            ke[i] = pr.solver.kinetic_energy(state)
    if grows:
        assert ke[160] > 4 * ke[40], ke
    else:
        assert ke[160] < 0.5 * ke[40], ke


# ---------------------------------------------------------------------------
# a five-field state through the framework
# ---------------------------------------------------------------------------
def test_fields_are_the_cases():
    pr = _prepare()
    assert pr.solver.FIELDS == ("vx", "vy", "vz", "p", "T")
    assert set(pr.state) == {"vx", "vy", "vz", "p", "T", "mask_vx",
                             "mask_vy", "mask_vz"}
    assert NavierStokes3D(api.runtime(n=N).configure("cavity")).FIELDS == \
        ("vx", "vy", "vz", "p")
    assert pr.config.shape == SHAPE and pr.config.h == 1.0 / SHAPE[0]
    nu, kappa = rc.viscosities(1e6, 0.7)
    assert (pr.config.nu, pr.config.kappa) == (nu, kappa)
    assert ns3d.host_params(pr.config)["kappa"] == np.float32(kappa)


def test_deployment_shape_and_dt():
    cfg = api.runtime(n=768, nz=768).configure("rayleigh_benard")
    assert cfg.shape == (128, 768, 768) and cfg.h == 1 / 128
    assert cfg.dt == pytest.approx(1.7022e-3, rel=1e-4)
    assert cfg.jacobi_iters == 60


def test_farm_members_equal_their_serial_runs():
    """Members of different Ra and seeds in one farm come out bitwise
    equal to their serial runs, T included."""
    jobs = ((5e3, 1, 12), (2e4, 2, 20), (1e5, 3, 7))
    rt = api.runtime(n=N, nz=N, n_slots=2)
    sids = [rt.submit("rayleigh_benard", steps=steps, aspect=ASPECT, ra=ra,
                      seed=seed, jacobi_iters=40)
            for ra, seed, steps in jobs]
    res = rt.drain()
    for sid, (ra, seed, steps) in zip(sids, jobs):
        pr = _prepare(ra=ra, seed=seed, jacobi_iters=40)
        state = pr.state
        for _ in range(steps):
            state = pr.step(state)
        got = res[sid]
        assert got.terminated == "steps" and got.steps_done == steps
        for f in pr.solver.FIELDS:
            np.testing.assert_array_equal(got.state[f],
                                          np.asarray(state[f]), err_msg=f)


def test_slot_io_moves_the_temperature():
    pr = _prepare()
    ex = EnsembleExecutor(pr.config, n_slots=2)
    host = {k: np.asarray(v) for k, v in pr.state.items()}
    host["T"] = host["T"] + 0.25
    ex.write_slots([(1, ns3d.host_params(pr.config), host)])
    back = ex.read_slots([1])[0]
    assert set(back) == set(host)
    np.testing.assert_array_equal(back["T"], host["T"])
    assert "T" in ex._dynamic


def test_health_sentinel_catches_a_nan_in_t():
    pr = _prepare()
    col = ns3d.HEALTH_DIAGS.index("finite")
    diag = pr.solver.health_diagnostics
    assert float(diag(pr.state)[col]) == 1.0
    bad = dict(pr.state, T=pr.state["T"].at[3, 4, 5].set(jnp.nan))
    assert float(diag(bad)[col]) == 0.0
    assert pr.solver.health_report(bad)["finite"] == 0.0


def test_codec_round_trips_a_five_field_request():
    sc = api.get_scenario("rayleigh_benard")
    req = sc.request(N, steps=5, aspect=ASPECT, ra=3e4, seed=9)
    assert {"T", "vx", "p"} <= set(req.init_state)
    back = decode_request(*encode_request(req))
    assert back.config == req.config and back.config.kappa > 0
    assert set(back.init_state) == set(req.init_state)
    for k, v in req.init_state.items():
        np.testing.assert_array_equal(back.init_state[k], v)


# ---------------------------------------------------------------------------
# the periodicity table and obs.perf's counts
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("case, periodic", [
    ("cavity", (False, False, True)),
    ("taylor_green", (True, True, True)),
    ("kelvin_helmholtz", (True, True, True)),
    ("rayleigh_benard", (False, True, True)),
])
def test_periodicity_table(case, periodic):
    """The domain, the wall masks and the BC table follow the table; for
    the three older cases they are what the solver built before it."""
    solver = NavierStokes3D(CFDConfig(shape=(8, 8, 8), case=case))
    assert ns3d.PERIODIC[case] == solver.domain.periodic == periodic
    state = solver.init_state()
    for a, f in enumerate(("vx", "vy", "vz")):
        m = np.asarray(state[f"mask_{f}"])
        edge = m[(slice(None),) * a + (-1,)]
        assert (edge == (1.0 if periodic[a] else 0.0)).all(), f
        assert m.sum() == (512 if periodic[a] else 448), f
    for f in solver.FIELDS:
        lo, hi = solver.bc[f]
        for a in range(3):
            assert (lo[a] is None) == (hi[a] is None) == periodic[a], (f, a)


def test_unknown_case_is_refused():
    with pytest.raises(ValueError, match="unknown case"):
        NavierStokes3D(CFDConfig(shape=(8, 8, 8), case="channel"))


def test_step_exchanges_count_the_temperatures_fills():
    rb = CFDConfig(shape=(16, 16, 16), case="rayleigh_benard")
    cav = CFDConfig(shape=(16, 16, 16), case="cavity")
    (w, calls, _), *rest = perf._step_exchanges(rb)
    assert (w, calls) == ((1, 1, 1), 7)
    assert perf._step_exchanges(cav)[0][1] == 3
    assert rest == perf._step_exchanges(cav)[1:]


# ---------------------------------------------------------------------------
# the benchmark cell at a tiny size
# ---------------------------------------------------------------------------
TINY = {"config": {"grid": [8, 48, 48], "dt": 0.03125}}


def test_benchmark_cell_runs_tiny_and_its_control_is_rejected():
    import run

    out, checks = run.run_cell("rb-g6-dns", 2 ** 31 + 19, 1.0, False,
                               require_tpu=False, overrides=TINY)
    assert out["correct"], out["checks"]
    assert {n for n, _, _ in checks} == {"state_gap", "temperature_gap"}
    assert set(out["metrics"]) == {"cell_updates_per_s", "setup_s"}
    assert out["compiles_in_window"] == 0
    bench = run.load_json(run.ROOT / "BENCHMARK.json")
    w, config, traffic = run.cell_files(bench, "rb-g6-dns")
    driver = run.load_module(run.HERE / "drivers" / "convection_loop.py")
    cell = run.Cell(w, {**config, **TINY["config"]}, traffic, 2 ** 31 + 19,
                    1.0, False, jax.devices()[:1])
    control = driver.control(cell)
    assert all(v > limit for _, v, limit in control), control


def test_roofline_count_is_52_bytes_a_cell():
    import work_convection

    cells = math.prod((128, 768, 768))
    assert work_convection.step_bytes(cells) == 52 * cells
    least, bound = work_convection.least_step_seconds(cells, 60,
                                                      "TPU v5 lite")
    assert bound == "bytes"
    assert least == pytest.approx(52 * cells / 819e9)


def test_step_span_carries_the_streamed_sweeps(monkeypatch):
    """On a TPU the layer's step span says how many sweeps stream; the
    platform and the size gate are steered here, the shape is lane-aligned
    and walled in x."""
    from repro import obs
    from repro.kernels import jacobi

    seen = []
    real_span, real_count = obs.span, perf.streamed_sweeps_per_step
    monkeypatch.setattr(jacobi, "_STAGED_BYTES", 0)
    monkeypatch.setattr(obs, "span", lambda name, **stats: (
        seen.append((name, stats)), real_span(name, **stats))[1])
    monkeypatch.setattr(perf, "streamed_sweeps_per_step",
                        lambda cfg, local, _: real_count(cfg, local, "tpu"))
    pr = api.runtime(n=16, nz=128).prepare("rayleigh_benard", aspect=2,
                                           jacobi_iters=8)
    assert pr.config.shape == (8, 16, 128)
    pr.step(pr.state)
    assert [s for n, s in seen if n == "runtime.step"] == [
        {"streamed_sweeps": 8}]
