"""The Jacobi sweep that reads p's face neighbours at p's own shape
(``halo.shifted_neighbours`` + ``jacobi.jacobi_pressure_unpadded``) is
bitwise the sweep over the padded copy (``exchange_pad`` +
``ops.jacobi_pressure``): periodic, walled, vmapped over farm members,
and split in x over four devices; and ``obs.perf.padded_sweeps_per_step``
counts the sweeps that still build the padded copy."""
import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cfd import cavity, taylor_green
from repro.cfd.ns3d import CFDConfig, NavierStokes3D
from repro.core.halo import exchange_pad, shifted_neighbours
from repro.kernels import ops
from repro.kernels.jacobi import jacobi_pressure_unpadded
from repro.obs import perf
from tests.helpers import run_with_devices

N, OMEGA = 16, 0.8
CONFIGS = Path(__file__).resolve().parents[1] / "benchmarks" / "chip" / "configs"


def _sweeps(specs, h):
    def shifted(p, rhs):
        return jacobi_pressure_unpadded(
            p, shifted_neighbours(p, specs), rhs, h=h, omega=OMEGA)

    def padded(p, rhs):
        return ops.jacobi_pressure(exchange_pad(p, (1, 1, 1), specs), rhs,
                                   h=h, omega=OMEGA)

    return shifted, padded


def _fields(shape, seed):
    kp, kr = jax.random.split(jax.random.PRNGKey(seed))
    return (jax.random.normal(kp, shape, jnp.float32),
            jax.random.normal(kr, shape, jnp.float32))


def _serial(case):
    cfg = (taylor_green.config(N, nz=N) if case == "taylor_green"
           else cavity.config(N))
    solver = NavierStokes3D(cfg)
    shifted, padded = _sweeps(solver._specs("p"), cfg.h)
    if case == "cavity_vmapped":
        # three farm members with different p and rhs, one vmapped sweep
        p, rhs = _fields((3,) + cfg.shape, 1)
        shifted, padded = jax.vmap(shifted), jax.vmap(padded)
    else:
        p, rhs = _fields(cfg.shape, 0)
    return [(np.asarray(jax.jit(shifted)(p, rhs)),
             np.asarray(jax.jit(padded)(p, rhs)))]


X_SPLIT = f"""
import json
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh
from repro.cfd import cavity, taylor_green
from repro.cfd.ns3d import NavierStokes3D
from repro.core.halo import exchange_pad, shifted_neighbours
from repro.kernels import ops
from repro.kernels.jacobi import jacobi_pressure_unpadded

mesh = Mesh(np.asarray(jax.devices()[:4]), ("shard",))
out = {{}}
for case, cfg in (("taylor_green", taylor_green.config({N}, nz={N})),
                  ("cavity", cavity.config({N}, nz=4))):
    cfg = cfg.__class__(**{{**cfg.__dict__,
                            "decomposition": ((0, "shard"),)}})
    solver = NavierStokes3D(cfg, mesh)
    specs = solver._specs("p")
    h = cfg.h

    def both(p, rhs):
        a = jacobi_pressure_unpadded(p, shifted_neighbours(p, specs),
                                     rhs, h=h, omega={OMEGA})
        b = ops.jacobi_pressure(exchange_pad(p, (1, 1, 1), specs), rhs,
                                h=h, omega={OMEGA})
        return a, b

    spec = solver.field_pspec
    f = jax.jit(jax.shard_map(both, mesh=mesh, in_specs=(spec, spec),
                              out_specs=(spec, spec), check_vma=False))
    kp, kr = jax.random.split(jax.random.PRNGKey(2))
    p = jax.random.normal(kp, cfg.shape, jnp.float32)
    rhs = jax.random.normal(kr, cfg.shape, jnp.float32)
    a, b = f(p, rhs)
    out[case] = [np.asarray(a).tolist(), np.asarray(b).tolist()]
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def x_split():
    out = run_with_devices(X_SPLIT, n_devices=4, timeout=540)
    return [tuple(np.asarray(v, np.float32) for v in pair)
            for pair in json.loads(out.strip().splitlines()[-1]).values()]


@pytest.mark.parametrize("case", [
    "taylor_green", "cavity", "cavity_vmapped",
    pytest.param("x_split", marks=pytest.mark.multidevice),
])
def test_shifted_sweep_is_bitwise_the_padded_sweep(case, request):
    pairs = (request.getfixturevalue("x_split") if case == "x_split"
             else _serial(case))
    for shifted, padded in pairs:
        assert shifted.shape == padded.shape
        np.testing.assert_array_equal(shifted, padded)


# ---------------------------------------------------------------------------
# the padded_sweeps counter
# ---------------------------------------------------------------------------
def _cell_config(name):
    """The solver configuration a benchmark cell runs (jnp template)."""
    cfg = json.loads((CONFIGS / f"{name}.json").read_text())
    nx, ny, nz = cfg["grid"]
    case = cfg["scenario"]
    build = taylor_green.config if case == "taylor_green" else cavity.config
    c = build(nx, nz=nz, jacobi_iters=cfg["jacobi_iters"])
    decomp = tuple(tuple(d) for d in (cfg["mesh"] or {}).get(
        "decomposition", ()))
    return dataclasses.replace(c, template="JNP", decomposition=decomp)


@pytest.mark.parametrize("name", ["tgv-dns-256", "ghia-cavity-farm",
                                  "tgv-dns-768-x4-v2"])
def test_no_cell_builds_a_padded_copy_of_p(name):
    assert perf.padded_sweeps_per_step(_cell_config(name)) == 0


@pytest.mark.parametrize("kw, trips", [
    (dict(template="3DBLOCK"), 8),
    (dict(fused_sweeps=2), 4),
    (dict(template="3DBLOCK", fused_sweeps=2), 4),
])
def test_padded_paths_count_every_loop_trip(kw, trips):
    cfg = CFDConfig(shape=(N, N, N), case="taylor_green", jacobi_iters=8,
                    **kw)
    assert perf.padded_sweeps_per_step(cfg) == trips


def _sweep_loop_shapes(cfg):
    """Shapes of the arrays made inside the step's loops (on the jnp
    template the Jacobi sweeps' is the only one), from its jaxpr."""
    from jax.extend.core import ClosedJaxpr, Jaxpr

    def subjaxprs(eqn):
        for v in eqn.params.values():
            for j in (v if isinstance(v, (tuple, list)) else (v,)):
                if isinstance(j, (Jaxpr, ClosedJaxpr)):
                    yield getattr(j, "jaxpr", j)

    def shapes(jaxpr, in_loop):
        for eqn in jaxpr.eqns:
            if in_loop:
                yield from (tuple(v.aval.shape) for v in eqn.outvars)
            for sub in subjaxprs(eqn):
                yield from shapes(sub, in_loop or eqn.primitive.name in (
                    "scan", "while"))

    solver = NavierStokes3D(cfg)
    step = jax.make_jaxpr(solver.make_step())(
        jax.eval_shape(solver.init_state))
    return set(shapes(step.jaxpr, False))


@pytest.mark.parametrize("fused_sweeps", [1, 2])
def test_the_counter_reads_the_lowered_loop(fused_sweeps):
    """A padded copy of p exists in the lowered sweeps exactly where the
    counter says one is built."""
    cfg = CFDConfig(shape=(N, N, N), case="taylor_green", jacobi_iters=8,
                    fused_sweeps=fused_sweeps)
    padded = (N + 2 * fused_sweeps,) * 3
    assert (padded in _sweep_loop_shapes(cfg)) == bool(
        perf.padded_sweeps_per_step(cfg))


@pytest.mark.parametrize("fused_sweeps, planes, permutes", [
    # 2 x 3 velocity pads, 3 divergence pads, 1 projection pad, and per
    # trip two one-wide faces of p (k = 1) or two two-wide faces of p
    # and of rhs (k = 2)
    (1, 6 + 3 + 2 * 8 + 1, 6 + 3 + 2 * 8 + 1),
    (2, 6 + 3 + 4 * 2 * 2 * 2 + 1, 6 + 3 + 4 * 2 * 2 + 1),
])
def test_x_split_halo_counts_are_unchanged(fused_sweeps, planes, permutes):
    cfg = CFDConfig(shape=(N, N, N), case="taylor_green", jacobi_iters=8,
                    fused_sweeps=fused_sweeps, decomposition={0: "shard"})
    active = {0: "shard"}
    assert perf.halo_bytes_per_step(cfg, active, {"shard": 4}) == \
        planes * N * N * 4
    assert perf.halo_permutes_per_step(cfg, active) == permutes


def test_y_split_sweeps_ship_faces_at_the_unpadded_shape():
    """Split after x, the sweeps' faces no longer carry x's ghost rows:
    the analytic bytes still equal the lowered step's permute bytes."""
    from repro.launch import hlo_cost

    cfg = CFDConfig(shape=(N, N, N), case="taylor_green", jacobi_iters=8,
                    decomposition={1: "shard"})
    text, active = perf.decomposed_step_hlo(
        cfg, n_slots=1, mesh_axes=(("slot", 1), ("shard", 4)))
    cost, status, _ = hlo_cost.safe_analyze(text, 4)
    assert status == "ok" and active == {1: "shard"}
    lowered = cost.collective_bytes["collective-permute"]
    assert lowered == perf.halo_bytes_per_step(cfg, active, {"shard": 4})
    padded = perf.halo_bytes_per_step(
        dataclasses.replace(cfg, template="3DBLOCK"), active, {"shard": 4})
    # 8 sweeps of two faces, each two of x's ghost rows of N values lighter
    assert padded - lowered == 8 * 2 * 2 * N * 4
