"""The farm's placement rules (``sim.ensemble``): where the slot axis
and each slot's grid axes go on a farm mesh.

Spec rules are pure functions of (mesh shape, slot count, field shape), so
a devices-free mesh stub covers the divisibility matrix on one CPU with no
subprocess.  The slot axis is guarded (an indivisible slot count runs
replicated); the grid axes raise (an indivisible grid would be
mis-sharded).
"""
import jax
import pytest
from hypothesis import given, settings, strategies as st
from jax.sharding import PartitionSpec as P

from repro.sim.ensemble import slot_field_spec, slot_spec
from tests.helpers import MeshStub


def test_slot_spec():
    mesh = MeshStub(data=4, model=2)
    assert slot_spec(mesh, 8) == P("data")
    assert slot_spec(mesh, 6) == P(None)        # 6 % 4 != 0 -> replicated


# ---------------------------------------------------------------------------
# slots x shards field specs (the 2-axis farm mesh)
# ---------------------------------------------------------------------------
def test_slot_field_spec_slot_times_shard():
    mesh = MeshStub(slot=2, shard=4)
    spec = slot_field_spec(mesh, 8, (16, 16, 4), ((0, "shard"),))
    assert spec == P("slot", "shard", None, None)


def test_slot_field_spec_two_axis_grid_decomposition():
    mesh = MeshStub(slot=2, sx=2, sy=2)
    spec = slot_field_spec(mesh, 4, (16, 16, 8), ((0, "sx"), (1, "sy")))
    assert spec == P("slot", "sx", "sy", None)


def test_slot_field_spec_undecomposed_grid():
    mesh = MeshStub(slot=4)
    assert slot_field_spec(mesh, 8, (16, 16, 4)) == \
        P("slot", None, None, None)


def test_slot_field_spec_indivisible_slots_replicate():
    """Slots never interact -> the slot axis is guarded, not an error."""
    mesh = MeshStub(slot=2, shard=4)
    spec = slot_field_spec(mesh, 3, (16, 16, 4), ((0, "shard"),))
    assert spec == P(None, "shard", None, None)


def test_slot_field_spec_indivisible_grid_raises():
    """Grid axes RAISE: halo code ppermutes assuming true shards, so a
    silently replicated axis would be mis-sharded, not just unparallel."""
    mesh = MeshStub(slot=2, shard=4)
    with pytest.raises(ValueError, match="not divisible"):
        slot_field_spec(mesh, 8, (10, 16, 4), ((0, "shard"),))


def test_slot_field_spec_unknown_axes_raise():
    mesh = MeshStub(slot=2, shard=4)
    with pytest.raises(ValueError, match="no slot axis"):
        slot_field_spec(mesh, 8, (16, 16, 4), ((0, "shard"),),
                            slot_axis="slots")
    with pytest.raises(ValueError, match="no axis 'model'"):
        slot_field_spec(mesh, 8, (16, 16, 4), ((0, "model"),))
    with pytest.raises(ValueError, match="slot axis"):
        slot_field_spec(mesh, 8, (16, 16, 4), ((0, "slot"),))


def test_slot_field_spec_bad_array_axis_raises():
    mesh = MeshStub(slot=2, shard=4)
    with pytest.raises(ValueError, match="array axis 3"):
        slot_field_spec(mesh, 8, (16, 16, 4), ((3, "shard"),))


def test_slot_field_spec_duplicate_array_axis_raises():
    """One grid axis mapped twice must raise, not silently keep the last
    mapping (dict() would dedup to half the requested parallelism)."""
    mesh = MeshStub(slot=2, sx=2, sy=2)
    with pytest.raises(ValueError, match="more than once"):
        slot_field_spec(mesh, 8, (16, 16, 4), ((0, "sx"), (0, "sy")))


def test_slot_field_spec_covers_eval_shape_state():
    """The rule applied over a real solver state tree (eval_shape — no
    arrays, no devices): every field of the slot-stacked ensemble state
    gets the same P(slot, shard, ...) placement."""
    from repro.cfd import cavity
    from repro.cfd.ns3d import NavierStokes3D

    solver = NavierStokes3D(cavity.config(16, jacobi_iters=20))
    shapes = jax.eval_shape(solver.init_state)
    mesh = MeshStub(slot=2, shard=4)
    specs = {k: slot_field_spec(mesh, 8, v.shape, ((0, "shard"),))
             for k, v in shapes.items()}
    assert set(specs) >= {"vx", "vy", "vz", "p"}
    for k, spec in specs.items():
        assert spec == P("slot", "shard", None, None), k


def test_slot_field_spec_matches_solver_field_pspec():
    """The farm's slot-stacked spec == P(slot, *solver.field_pspec): the
    two layers agree on the grid placement by construction."""
    from repro.cfd import cavity
    from repro.cfd.ns3d import NavierStokes3D
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1, 1), ("slot", "shard"))
    solver = NavierStokes3D(
        cavity.config(16, jacobi_iters=20, decomposition=((0, "shard"),)),
        mesh)
    stacked = slot_field_spec(mesh, 4, solver.config.shape,
                                  solver.config.decomposition)
    assert tuple(stacked)[1:] == tuple(solver.field_pspec)


@settings(max_examples=25)
@given(n=st.integers(5, 64), shards=st.integers(2, 8),
       slots=st.integers(1, 8))
def test_indivisible_grid_shard_combinations_raise_property(n, shards, slots):
    """Every layer that could mis-shard an indivisible grid refuses
    instead: the spec rule raises, and the driver's Domain validation
    raises — never a silently replicated 'shard'."""
    if n % shards == 0:
        n += 1                      # force indivisibility
        if n % shards == 0:         # (can't happen, but keep it obvious)
            return
    mesh = MeshStub(slot=2, shard=shards)
    with pytest.raises(ValueError, match="not divisible"):
        slot_field_spec(mesh, slots, (n, 16, 4), ((0, "shard"),))

    from repro.core.driver import Domain, GridDriver

    with pytest.raises(ValueError, match="not divisible"):
        GridDriver(Domain(shape=(n, 16, 4), decomposition={0: "shard"}),
                   mesh)
