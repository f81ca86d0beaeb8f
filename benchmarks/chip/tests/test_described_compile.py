"""Each cell's step, and the reference its check runs, compile at the timed
shape for a described TPU v5e (``tgv768-x4`` for a described 2x2 mesh) and
fit in a chip's 16 GB.  Nothing runs on a chip.  Everything built from the
topology is built in fixtures, so only the worker that runs this file loads
the TPU library."""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import reference
import run

HBM = 16e9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _config(name):
    return run.load_json(run.HERE / "configs" / f"{name}.json")


def _bytes(compiled):
    m = compiled.memory_analysis()
    return m.argument_size_in_bytes + m.output_size_in_bytes + \
        m.temp_size_in_bytes


def _dns_step(cfg, mesh):
    from repro.cfd import taylor_green
    from repro.cfd.ns3d import NavierStokes3D, params_from_config

    n = cfg["grid"][0]
    c = taylor_green.config(n, nz=cfg["grid"][2], nu=cfg["nu"], dt=cfg["dt"],
                            jacobi_iters=cfg["jacobi_iters"])
    decomp = tuple(tuple(d) for d in (cfg["mesh"] or {}).get(
        "decomposition", ()))
    c = dataclasses.replace(c, template="JNP", decomposition=decomp)
    solver = NavierStokes3D(c, mesh if decomp else None)
    example = jax.eval_shape(solver.init_state)
    params = params_from_config(c)
    return solver, example, params


@pytest.mark.parametrize("name", ["tgv-dns-256", "tgv-dns-768-x4"])
def test_dns_step_and_reference_compile(topo, name):
    cfg = _config(name)
    chips = cfg["mesh"]["shape"][0] if cfg["mesh"] else 1
    devices = np.asarray(topo.devices[:chips])
    mesh = Mesh(devices, ("shard",))
    solver, example, params = _dns_step(cfg, mesh)
    sh = NamedSharding(mesh, P("shard") if chips > 1 else P())
    shapes = {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=sh)
              for k, v in example.items()}
    pshapes = {k: jax.ShapeDtypeStruct((), jnp.float32,
                                       sharding=NamedSharding(mesh, P()))
               for k in params}
    step = solver.driver.sharded_step_tree(solver._step_local, example,
                                           params)
    compiled = step.lower(shapes, pshapes).compile()
    # the window keeps two steps' outputs alive beside the state
    m = compiled.memory_analysis()
    assert m.argument_size_in_bytes + 2 * m.output_size_in_bytes + \
        m.temp_size_in_bytes < HBM
    if chips > 1:
        assert "collective-permute" in compiled.as_text()

    kw = dict(case="taylor_green", h=cfg["extent"] / cfg["grid"][0],
              dt=cfg["dt"], nu=cfg["nu"], lid=0.0,
              sweeps=cfg["jacobi_iters"], masks=None)
    ring = "shard" if chips > 1 else None
    body = jax.shard_map(
        lambda s: reference.evolve(s, cfg["check"]["steps"], ring=ring,
                                   **kw), mesh=mesh,
        in_specs=({f: P(ring) for f in reference.FIELDS},),
        out_specs={f: P(ring) for f in reference.FIELDS}, check_vma=False)
    rshapes = {f: jax.ShapeDtypeStruct(tuple(cfg["grid"]), jnp.float32,
                                       sharding=NamedSharding(mesh, P(ring)))
               for f in reference.FIELDS}
    assert _bytes(jax.jit(body).lower(rshapes).compile()) < HBM


def test_farm_step_compiles(topo):
    from repro.cfd import cavity
    from repro.cfd.ns3d import PARAM_KEYS, NavierStokes3D
    from repro.sim.ensemble import make_ensemble_step

    cfg = _config("ghia-cavity-farm")
    one = NamedSharding(Mesh(np.asarray(topo.devices[:1]), ("x",)), P())
    nx, _, nz = cfg["grid"]
    c = dataclasses.replace(cavity.config(nx, nz=nz), template="JNP",
                            jacobi_iters=cfg["jacobi_iters"])
    solver = NavierStokes3D(c)
    slots = cfg["slots"]
    run_k = make_ensemble_step(solver, n_slots=slots)
    example = jax.eval_shape(solver.init_state)
    shapes = {k: jax.ShapeDtypeStruct((slots,) + v.shape, v.dtype,
                                      sharding=one)
              for k, v in example.items()}
    ps = {k: jax.ShapeDtypeStruct((slots,), jnp.float32, sharding=one)
          for k in PARAM_KEYS}
    compiled = run_k.lower(shapes, ps, jax.ShapeDtypeStruct(
        (), jnp.int32, sharding=one)).compile()
    assert _bytes(compiled) < HBM
