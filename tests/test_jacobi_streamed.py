"""The Jacobi sweep that streams p's x-planes through VMEM
(``jacobi.jacobi_pressure_streamed``, interpreted here) agrees with the
unpadded jnp sweep (``jacobi_pressure_unpadded``) to float32 rounding:
periodic, walled in x with the faces the Neumann rule gives, split in x
over four devices with the faces ``halo`` ships, and vmapped over
members; three whole steps with the kernel forced stay
within the DNS cells' ``state_gap`` limit; and
``obs.perf.streamed_sweeps_per_step`` counts the sweeps that take it."""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api, obs
from repro.cfd import cavity, taylor_green
from repro.cfd import ns3d
from repro.cfd.ns3d import CFDConfig, NavierStokes3D
from repro.core.halo import AxisSpec, ghost_faces, shifted_neighbours
from repro.kernels import jacobi
from repro.kernels.jacobi import (jacobi_pressure_streamed,
                                  jacobi_pressure_unpadded, streams)
from repro.obs import perf
from tests.helpers import run_with_devices
from tests.test_jacobi_shifted import CONFIGS, _cell_config, _fields

SHAPE, H, OMEGA = (16, 8, 128), 0.1, 0.8
ULPS = 4
PERIODIC = [AxisSpec(a, None, True) for a in range(3)]


def _assert_within_ulps(got, want):
    """Equal to ``ULPS`` float32 ulps of the field's largest value."""
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape
    tol = ULPS * np.spacing(np.abs(want).max())
    np.testing.assert_allclose(got, want, rtol=0, atol=tol)


def _unpadded(p, rhs):
    return jacobi_pressure_unpadded(p, shifted_neighbours(p, PERIODIC), rhs,
                                    h=H, omega=OMEGA)


@pytest.mark.parametrize("block_planes, shape", [
    (1, SHAPE), (4, SHAPE), (16, SHAPE),
    (2, (8, 32, 128)),        # four tile rows, y wraps across them
    (2, (4, 24, 256)),        # three tile rows, two lane tiles
])
def test_periodic_sweep_matches_the_jnp_sweep(block_planes, shape):
    p, rhs = _fields(shape, 0)
    got = jax.jit(lambda p, r: jacobi_pressure_streamed(
        p, r, h=H, omega=OMEGA, block_planes=block_planes,
        interpret=True))(p, rhs)
    _assert_within_ulps(got, _unpadded(p, rhs))


def test_faces_are_x_outer_planes():
    p, rhs = _fields(SHAPE, 1)
    lo, hi = jax.random.normal(jax.random.PRNGKey(3), (2, 1) + SHAPE[1:])
    nbrs = shifted_neighbours(p, PERIODIC)
    nbrs[0] = (jnp.concatenate([lo, p[:-1]]), jnp.concatenate([p[1:], hi]))
    want = jacobi_pressure_unpadded(p, nbrs, rhs, h=H, omega=OMEGA)
    got = jacobi_pressure_streamed(p, rhs, (lo, hi), h=H, omega=OMEGA,
                                   block_planes=4, interpret=True)
    _assert_within_ulps(got, want)


def _walled_x_specs(shape):
    """p's specs in a convection layer: Neumann walls in x, y and z
    periodic."""
    return NavierStokes3D(CFDConfig(shape=shape, case="rayleigh_benard")
                          )._specs("p")


@pytest.mark.parametrize("sweeps", [1, 3])
def test_walled_x_sweep_matches_the_jnp_sweep(sweeps):
    """Walls in x: the kernel's outer planes are the faces the Neumann
    rule gives (``halo.ghost_faces``), the jnp sweep reads the same rule
    from p's edge rows."""
    specs = _walled_x_specs(SHAPE)
    assert not specs[0].periodic and specs[0].mesh_axis is None
    p, rhs = _fields(SHAPE, 7)
    got = want = p
    for _ in range(sweeps):
        got = jacobi_pressure_streamed(got, rhs, ghost_faces(got, specs[0]),
                                       h=H, omega=OMEGA, block_planes=4,
                                       interpret=True)
        want = jacobi_pressure_unpadded(want, shifted_neighbours(want, specs),
                                        rhs, h=H, omega=OMEGA)
    _assert_within_ulps(got, want)


def test_two_vmapped_members_match():
    p, rhs = _fields((2,) + SHAPE, 2)
    got = jax.vmap(lambda a, b: jacobi_pressure_streamed(
        a, b, h=H, omega=OMEGA, block_planes=4, interpret=True))(p, rhs)
    _assert_within_ulps(got, jax.vmap(_unpadded)(p, rhs))


@pytest.mark.parametrize("block_planes, shape", [
    (3, SHAPE),               # no short last block: 3 does not divide 16
    (4, (16, 12, 128)),       # y not whole sublane tiles
    (4, (16, 8, 96)),         # z not whole lanes
])
def test_planes_the_kernel_refuses(block_planes, shape):
    p, rhs = _fields(shape, 0)
    with pytest.raises(ValueError):
        jacobi_pressure_streamed(p, rhs, h=H, block_planes=block_planes,
                                 interpret=True)


X_SPLIT = f"""
import json
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh
from repro.cfd import taylor_green
from repro.cfd import ns3d
from repro.cfd.ns3d import CFDConfig, NavierStokes3D
from repro.core.halo import ghost_faces, shifted_neighbours
from repro.kernels.jacobi import (jacobi_pressure_streamed,
                                  jacobi_pressure_unpadded)

mesh = Mesh(np.asarray(jax.devices()[:4]), ("shard",))
cfg = taylor_green.config(32, nz=128)
cfg = cfg.__class__(**{{**cfg.__dict__, "decomposition": ((0, "shard"),)}})
solver = NavierStokes3D(cfg, mesh)
specs = solver._specs("p")


def both(p, rhs):
    a = jacobi_pressure_streamed(p, rhs, ghost_faces(p, specs[0]),
                                 h=cfg.h, omega={OMEGA}, block_planes=4,
                                 interpret=True)
    b = jacobi_pressure_unpadded(p, shifted_neighbours(p, specs), rhs,
                                 h=cfg.h, omega={OMEGA})
    return a, b


spec = solver.field_pspec
f = jax.jit(jax.shard_map(both, mesh=mesh, in_specs=(spec, spec),
                          out_specs=(spec, spec), check_vma=False))
kp, kr = jax.random.split(jax.random.PRNGKey(4))
p = jax.random.normal(kp, cfg.shape, jnp.float32)
rhs = jax.random.normal(kr, cfg.shape, jnp.float32)
a, b = f(p, rhs)
print(json.dumps([np.asarray(a).tolist(), np.asarray(b).tolist()]))
"""


@pytest.mark.multidevice
def test_x_split_over_four_devices_reads_the_ghost_faces():
    out = run_with_devices(X_SPLIT, n_devices=4, timeout=540)
    got, want = json.loads(out.strip().splitlines()[-1])
    _assert_within_ulps(np.asarray(got, np.float32),
                        np.asarray(want, np.float32))


def _forced(monkeypatch) -> list:
    """Take the TPU branch of the step's sweep choice on the CPU, for a p
    of any size; the list returned grows by one each time the kernel is
    traced."""
    traced, kernel = [], ns3d.jacobi_pressure_streamed
    monkeypatch.setattr(jacobi, "_STAGED_BYTES", 0)
    monkeypatch.setattr(jax.lax, "platform_dependent",
                        lambda *args, tpu, default: tpu(*args))
    monkeypatch.setattr(ns3d, "jacobi_pressure_streamed", lambda *a, **k: (
        traced.append(a[0].shape), kernel(*a, **k))[1])
    return traced


def test_three_steps_with_the_kernel_forced_stay_within_state_gap(
        monkeypatch):
    """A lane-aligned Taylor-Green, three steps, the streamed kernel
    interpreted in every sweep against the jnp sweeps: within
    ``tgv-dns-256``'s ``state_gap`` limit, each field over its largest
    value."""
    cfg = taylor_green.config(16, nz=128, jacobi_iters=8)
    states, traced = {}, []
    for name in ("jnp", "streamed"):
        if name == "streamed":
            traced = _forced(monkeypatch)
            cfg = dataclasses.replace(cfg, interpret=True)
        solver = NavierStokes3D(cfg)
        step = jax.jit(solver.make_step())
        st = solver.init_state()
        for _ in range(3):
            st = step(st)
        states[name] = st
    assert traced == [cfg.shape]
    limit = json.loads(
        (CONFIGS / "tgv-dns-256.json").read_text())["check"]["limits"]
    for f in NavierStokes3D.FIELDS:
        want = np.asarray(states["jnp"][f])
        gap = np.abs(np.asarray(states["streamed"][f]) - want).max()
        assert gap <= limit["state_gap"] * np.abs(want).max(), (f, gap)


# ---------------------------------------------------------------------------
# the choice and its counter
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name, local, want", [
    ("tgv-dns-768-x4-v2", (192, 768, 768), 60),
    ("tgv-dns-256", (256, 256, 256), 0),    # 64 MiB: XLA stages it
    ("tgv-dns-256", (264, 256, 256), 60),   # past the staged bytes
])
def test_dns_cells_stream_on_a_tpu_past_the_staged_bytes(name, local,
                                                         want):
    cfg = _cell_config(name)
    assert perf.streamed_sweeps_per_step(cfg, local, "tpu") == want
    assert perf.streamed_sweeps_per_step(cfg, local, "cpu") == 0


def test_convection_cell_streams_its_walled_x_sweeps():
    """The ``rb-g6-dns`` layer, 128 x 768 x 768 with walls in x: every
    sweep streams on a TPU, none on the CPU."""
    from repro import api

    cfg = api.runtime(n=768, nz=768).configure("rayleigh_benard")
    assert cfg.shape == (128, 768, 768)
    assert streams(cfg.shape, _walled_x_specs(cfg.shape))
    assert perf.streamed_sweeps_per_step(cfg, cfg.shape, "tpu") == 60
    assert perf.streamed_sweeps_per_step(cfg, cfg.shape, "cpu") == 0
    walled_y = cavity.config(768, nz=768)
    assert perf.streamed_sweeps_per_step(walled_y, walled_y.shape,
                                         "tpu") == 0


def _tgv(**kw):
    return dataclasses.replace(taylor_green.config(256, nz=256), **kw)


@pytest.mark.parametrize("cfg, local", [
    (_cell_config("ghia-cavity-farm"), (128, 128, 4)),
    (cavity.config(256, nz=256), (256, 256, 256)),    # walls in y
    (_tgv(decomposition=((1, "shard"),)), (256, 64, 256)),
    (_tgv(template="3DBLOCK"), (256, 256, 256)),
    (_tgv(fused_sweeps=2), (256, 256, 256)),
    (_tgv(), (256, 256, 100)),
], ids=["farm-member", "walled-y", "y-split", "3dblock", "fused-2",
        "z-not-lanes"])
def test_other_steps_keep_the_jnp_sweep(monkeypatch, cfg, local):
    """Whatever p's size: the shapes and boundaries the kernel cannot
    take."""
    monkeypatch.setattr(jacobi, "_STAGED_BYTES", 0)
    assert perf.streamed_sweeps_per_step(cfg, local, "tpu") == 0


def test_the_counter_is_the_steps_choice(monkeypatch):
    """The step's choice and the counter read the same predicate."""
    monkeypatch.setattr(jacobi, "_STAGED_BYTES", 0)
    for cfg in (taylor_green.config(16, nz=128), cavity.config(16, nz=128),
                CFDConfig(shape=(16, 8, 128), case="rayleigh_benard")):
        solver = NavierStokes3D(cfg)
        assert streams(cfg.shape, solver._specs("p")) == bool(
            perf.streamed_sweeps_per_step(cfg, cfg.shape, "tpu"))


def _step_stats(monkeypatch, platform):
    seen = []
    real_span, real_count = obs.span, perf.streamed_sweeps_per_step
    monkeypatch.setattr(jacobi, "_STAGED_BYTES", 0)
    monkeypatch.setattr(obs, "span", lambda name, **stats: (
        seen.append((name, stats)), real_span(name, **stats))[1])
    monkeypatch.setattr(perf, "streamed_sweeps_per_step",
                        lambda cfg, local, _: real_count(cfg, local,
                                                         platform))
    rt = api.runtime(n=16, nz=128)
    pr = rt.prepare("taylor_green", jacobi_iters=8)
    pr.step(pr.state)
    return [s for n, s in seen if n == "runtime.step"]


@pytest.mark.parametrize("platform, want", [
    ("tpu", {"streamed_sweeps": 8}),
    ("cpu", {}),
])
def test_step_span_carries_streamed_sweeps_only_when_nonzero(
        monkeypatch, platform, want):
    assert _step_stats(monkeypatch, platform) == [want]
