"""Compiles for a described TPU v5e: what the chip's compiler accepts.

Nothing here runs on a chip.  The TPU compiler that ships with jaxlib
compiles for a topology that is described, not attached, so these tests
catch — at no chip time — a main-path program the chip would refuse, or
one that no longer fits its 16 GB.  Everything built from the topology is
built in fixtures, never at import: only the test worker that runs this
file loads the TPU library.
"""
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.cfd import cavity, taylor_green
from repro.cfd.ns3d import PARAM_KEYS, NavierStokes3D, params_from_config
from repro.core import autotune
from repro.core.rooflinemodel import V5E
from repro.kernels import jacobi, ops, stencil3d
from repro.obs import perf


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
    # a compile for a described chip cannot be read back from the
    # persistent cache without that chip: keep these out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shapes(tree, sharding):
    return {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=sharding)
            for k, v in tree.items()}


def _jacobi_loop_arrays(hlo: str) -> list[tuple[tuple, int]]:
    """``(dims, lane_dim)`` of every array the compiled Jacobi loop
    materialises: the results of its body's instructions."""
    bodies = {re.search(r"body=%([\w.\-]+)", line).group(1)
              for line in hlo.splitlines()
              if " while(" in line and "/jacobi/" in line}
    assert bodies, "no Jacobi while loop in the compiled step"
    arrays, current = [], None
    for line in hlo.splitlines():
        head = re.match(r"^(?:ENTRY )?%([\w.\-]+) ", line)
        if head and line.rstrip().endswith("{"):
            current = head.group(1)
        elif current in bodies and " = " in line:
            result = line.split(" = ", 1)[1].split(" ", 1)[0]
            if result.startswith("("):   # a multi-output fusion's tuple
                result = line.split(" = ", 1)[1].split(") ", 1)[0]
            for dims, layout in re.findall(
                    r"f32\[([\d,]*)\]\{([\d,]*)", result):
                dims = tuple(int(d) for d in dims.split(",") if d)
                if dims:
                    arrays.append((dims, int(layout.split(",")[0])))
    return arrays


def _assert_sweeps_read_unpadded_p(compiled, local_shape):
    """No array of the loop is a padded p (an axis ``n + 2`` wide) or a
    one-wide ghost strip on the lane axis."""
    padded = {n + 2 for n in local_shape}
    for dims, lane in _jacobi_loop_arrays(compiled.as_text()):
        assert not padded & set(dims), (dims, local_shape)
        assert dims[lane] > 1 or all(d == 1 for d in dims), dims


@pytest.mark.parametrize("config", [
    pytest.param(lambda: taylor_green.config(256, nz=256), id="taylor_green-256^3"),
    pytest.param(lambda: cavity.config(256), id="cavity-256x256x4"),
])
def test_jnp_step_compiles_and_fits_one_chip(one_chip, config):
    """The ns3d step on the default (jnp) template at deployment size."""
    solver = NavierStokes3D(config())
    state = _shapes(jax.eval_shape(solver.init_state), one_chip)
    params = {k: jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
              for k in PARAM_KEYS}
    compiled = jax.jit(solver._step_local).lower(state, params).compile()
    ma = compiled.memory_analysis()
    used = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert 0 < used <= V5E.hbm_bytes
    _assert_sweeps_read_unpadded_p(compiled, solver.config.shape)


def test_x_split_768_sweeps_read_unpadded_p(topo):
    """768^3 split in x over the described 2x2: the sweeps read the
    permuted x faces and p itself, with no 194-row p padded in x and no
    770-wide rows."""
    mesh = Mesh(topo.devices[:4], ("shard",))
    cfg = dataclasses.replace(taylor_green.config(768, nz=768),
                              template="JNP", decomposition=((0, "shard"),))
    solver = NavierStokes3D(cfg, mesh)
    example = jax.eval_shape(solver.init_state)
    params = params_from_config(cfg)
    field = NamedSharding(mesh, P("shard"))
    shapes = _shapes(example, field)
    scalars = {k: jax.ShapeDtypeStruct((), jnp.float32,
                                       sharding=NamedSharding(mesh, P()))
               for k in params}
    step = solver.driver.sharded_step_tree(solver._step_local, example,
                                           params)
    compiled = step.lower(shapes, scalars).compile()
    assert "collective-permute" in compiled.as_text()
    _assert_sweeps_read_unpadded_p(compiled, (192, 768, 768))


@pytest.mark.xfail(strict=True, reason="3DBLOCK does not lower for the TPU "
                   "yet: its halo-expanded blocks are not (8, 128)-aligned. "
                   "When this passes, reconsider backend='auto'.")
def test_3dblock_jacobi_compiles(one_chip):
    """The Pallas template of one main-path kernel, compiled (not
    interpreted) at 256^3 with the tile the autotuner picks for a v5e."""
    n = 256
    desc = stencil3d.DESCRIPTORS["JACOBI_PRESSURE"]
    tile = autotune.tile_for(desc, (n, n, n), chip=V5E).tile
    p = jax.ShapeDtypeStruct((n + 2,) * 3, jnp.float32, sharding=one_chip)
    rhs = jax.ShapeDtypeStruct((n,) * 3, jnp.float32, sharding=one_chip)

    def sweep(p, rhs):
        return ops.jacobi_pressure(p, rhs, h=2 * jnp.pi / n,
                                   template="3DBLOCK", interpret=False,
                                   tile=tile)

    jax.jit(sweep).lower(p, rhs).compile()


def test_slot_io_programs_compile_in_place(one_chip):
    """The farm's slot I/O at the benchmark's size (256 cavity slots of
    128 x 128 x 4): the fresh-admission update aliases the resident batch
    (donated, no copy of it), and the harvest gathers and the readmission
    scatter compile at the shortest and the longest round."""
    from repro.sim import ensemble

    n = 256
    solver = NavierStokes3D(cavity.config(128))
    one = jax.eval_shape(solver.init_state)
    state = {k: jax.ShapeDtypeStruct((n,) + v.shape, v.dtype,
                                     sharding=one_chip)
             for k, v in one.items()}
    out = {k: one_chip for k in state}
    mask = jax.ShapeDtypeStruct((n,), jnp.bool_, sharding=one_chip)
    fresh = jax.jit(ensemble._write_fresh, donate_argnums=0,
                    out_shardings=out).lower(
        state, _shapes(one, one_chip), mask).compile()
    batch = sum(v.size * v.dtype.itemsize for v in state.values())
    assert fresh.memory_analysis().alias_size_in_bytes == batch
    dynamic = {k: state[k] for k in NavierStokes3D.FIELDS}
    rows = jax.jit(ensemble._write_rows, donate_argnums=0, out_shardings=out)
    for b in (1, n):
        idx = jax.ShapeDtypeStruct((b,), jnp.int32, sharding=one_chip)
        got = ensemble._GATHER.lower(dynamic, idx).compile()
        assert got.memory_analysis().output_size_in_bytes >= \
            b * sum(v.size // n * v.dtype.itemsize for v in dynamic.values())
        rows.lower(state, idx, {
            k: jax.ShapeDtypeStruct((b,) + v.shape[1:], v.dtype,
                                    sharding=one_chip)
            for k, v in state.items()}).compile()


def _jacobi_loop_bodies(hlo: str) -> dict[str, list[str]]:
    """The instruction lines of each compiled Jacobi loop's body."""
    bodies = {re.search(r"body=%([\w.\-]+)", line).group(1): []
              for line in hlo.splitlines()
              if " while(" in line and "/jacobi/" in line}
    current = None
    for line in hlo.splitlines():
        head = re.match(r"^(?:ENTRY )?%([\w.\-]+) ", line)
        if head and line.rstrip().endswith("{"):
            current = head.group(1)
        elif current in bodies and " = " in line:
            bodies[current].append(line)
    assert bodies, "no Jacobi while loop in the compiled step"
    return bodies


def _assert_sweeps_stream(compiled, local_shape, streamed: bool):
    """Each Jacobi loop runs the streamed kernel (one Mosaic custom call)
    or none, and copies no p-sized array when it does."""
    p = "f32[" + ",".join(map(str, local_shape)) + "]"
    for body in _jacobi_loop_bodies(compiled.as_text()).values():
        kernels = [ln for ln in body if '"tpu_custom_call"' in ln]
        assert len(kernels) == int(streamed), kernels
        if streamed:
            copies = [ln for ln in body if re.search(
                r" = \S*" + re.escape(p) + r"\S* copy(-start)?\(", ln)]
            assert not copies, copies


@pytest.mark.parametrize("config, staged_bytes, streamed", [
    pytest.param(lambda: taylor_green.config(256, nz=256), None, False,
                 id="taylor_green-256^3"),
    pytest.param(lambda: taylor_green.config(256, nz=256), 0, True,
                 id="taylor_green-256^3-streamed"),
    pytest.param(lambda: cavity.config(256), 0, False,
                 id="cavity-256x256x4"),
])
def test_jnp_step_streams_lane_aligned_periodic_sweeps(
        one_chip, monkeypatch, config, staged_bytes, streamed):
    """The streamed kernel lowers through Mosaic in the 256^3 step where
    the counter says it runs: not for the 64 MiB p that XLA stages on
    chip itself, but once the size gate is lowered; the walled cavity
    keeps the jnp sweep either way."""
    if staged_bytes is not None:
        monkeypatch.setattr(jacobi, "_STAGED_BYTES", staged_bytes)
    solver = NavierStokes3D(config())
    state = _shapes(jax.eval_shape(solver.init_state), one_chip)
    params = {k: jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
              for k in PARAM_KEYS}
    compiled = jax.jit(solver._step_local).lower(state, params).compile()
    shape = solver.config.shape
    assert bool(perf.streamed_sweeps_per_step(solver.config, shape,
                                              "tpu")) == streamed
    _assert_sweeps_stream(compiled, shape, streamed)


def test_x_split_768_sweeps_stream_with_the_same_halo(topo):
    """768^3 split in x over the described 2x2: each sweep is the
    streamed kernel fed the two permuted x faces, with no p-sized copy in
    the loop, and the step ships the bytes the halo counter says."""
    from repro.launch import hlo_cost

    mesh = Mesh(topo.devices[:4], ("shard",))
    cfg = dataclasses.replace(taylor_green.config(768, nz=768),
                              template="JNP", decomposition=((0, "shard"),))
    solver = NavierStokes3D(cfg, mesh)
    example = jax.eval_shape(solver.init_state)
    params = params_from_config(cfg)
    scalars = {k: jax.ShapeDtypeStruct((), jnp.float32,
                                       sharding=NamedSharding(mesh, P()))
               for k in params}
    step = solver.driver.sharded_step_tree(solver._step_local, example,
                                           params)
    compiled = step.lower(_shapes(example, NamedSharding(mesh, P("shard"))),
                          scalars).compile()
    local = (192, 768, 768)
    assert perf.streamed_sweeps_per_step(cfg, local, "tpu") == 60
    _assert_sweeps_stream(compiled, local, True)
    cost, status, _ = hlo_cost.safe_analyze(compiled.as_text(), 4)
    assert status == "ok"
    assert cost.collective_bytes["collective-permute"] == \
        perf.halo_bytes_per_step(cfg, {0: "shard"}, {"shard": 4}) == \
        306_708_480
