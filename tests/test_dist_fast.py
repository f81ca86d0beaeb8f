"""Fast single-device coverage of ``repro.dist``.

The multi-device subprocess tests (test_substrate / test_dist_equivalence)
prove the distributed *execution*; these tests pin the substrate's *rules*
on the plain 1-CPU session so CPU-only CI exercises ``repro.dist`` on
every run:

  * spec builders are pure functions of (tree paths, leaf shapes, mesh
    shape) — ``jax.eval_shape`` param trees plus a devices-free mesh stub
    cover the full divisibility-guard matrix with zero subprocesses;
  * ``quantize_int8``/``dequantize_int8`` round-trip and error-feedback
    bounds are hypothesis properties.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st
from jax.sharding import PartitionSpec as P

from repro.configs.registry import get_config
from repro.dist import sharding as shd
from repro.dist.compression import (
    dequantize_int8, ef_allreduce_mean, quantize_int8, wire_bytes,
)
from repro.models import model
from tests.helpers import MeshStub


def _llama_specs(mesh, global_batch=8):
    cfg = get_config("llama3-8b")
    shard = shd.make_shard_cfg(mesh, cfg, global_batch=global_batch)
    shapes = jax.eval_shape(
        lambda: model.init_params(cfg, jax.random.PRNGKey(0)))
    return cfg, shard, shd.param_spec_tree(shapes, cfg, mesh, shard)


# ---------------------------------------------------------------------------
# sharding rules (mesh-geometry sweep, no devices needed)
# ---------------------------------------------------------------------------
def test_param_specs_fsdp_tp_layout():
    mesh = MeshStub(data=2, model=4)
    _, _, specs = _llama_specs(mesh)
    stack = specs["stack"]["layers"]
    assert stack["attn"]["wq"] == P(None, "data", "model", None)
    assert stack["attn"]["wk"] == P(None, "data", "model", None)  # 8 % 4 == 0
    assert stack["attn"]["wo"] == P(None, "model", None, "data")
    assert stack["ffn"]["gate"]["w"] == P(None, "data", "model")
    assert stack["ffn"]["down"]["w"] == P(None, "model", "data")
    assert specs["embed"]["table"] == P("model", "data")
    assert specs["unembed"]["w"] == P("data", "model")
    assert specs["final_norm"]["scale"] == P()


def test_param_specs_divisibility_guard_wide_tp():
    """kv_heads=8 over model=16: the guard replicates instead of erroring."""
    mesh = MeshStub(data=2, model=16)
    _, _, specs = _llama_specs(mesh)
    stack = specs["stack"]["layers"]
    assert stack["attn"]["wk"] == P(None, "data", None, None)   # 8 % 16 != 0
    assert stack["attn"]["wq"] == P(None, "data", "model", None)  # 32 % 16


def test_cache_specs_seq_guard():
    cfg = get_config("llama3-8b")
    mesh = MeshStub(data=2, model=4)
    shard = shd.make_shard_cfg(mesh, cfg, global_batch=8)
    mk = lambda s: jax.eval_shape(
        lambda: model.init_caches(cfg, 8, s, jnp.bfloat16))
    assert shd.cache_spec_tree(mk(1024), cfg, mesh, shard).k == \
        P(None, "data", "model", None, None)
    # sequence not divisible by tp=4 -> seq dim stays replicated
    assert shd.cache_spec_tree(mk(30), cfg, mesh, shard).k == \
        P(None, "data", None, None, None)


def test_batch_specs_and_non_divisible_batch():
    cfg = get_config("llama3-8b")
    mesh = MeshStub(data=4, model=2)
    batch = {"tokens": jax.ShapeDtypeStruct((8, 64), jnp.int32)}
    shard = shd.make_shard_cfg(mesh, cfg, global_batch=8)
    assert shard.batch_sharded
    assert shd.batch_spec_tree(batch, mesh, shard)["tokens"] == \
        P("data", None)
    shard3 = shd.make_shard_cfg(mesh, cfg, global_batch=3)  # 3 % 4 != 0
    assert not shard3.batch_sharded
    assert shd.batch_spec_tree(batch, mesh, shard3)["tokens"] == \
        P(None, None)


def test_make_shard_cfg_modes():
    cfg = get_config("llama3-8b")
    mesh = MeshStub(pod=2, data=2, model=2)
    fsdp = shd.make_shard_cfg(mesh, cfg, global_batch=8)
    assert fsdp.dp == ("pod", "data") and fsdp.tp == "model"
    assert not fsdp.replicate_params
    dp = shd.make_shard_cfg(mesh, cfg, global_batch=8, mode="dp")
    assert dp.replicate_params and dp.tp is None
    assert tuple(dp.dp_axes) == ("pod", "data", "model")
    # dp-mode params are replicated regardless of divisibility
    shapes = jax.eval_shape(
        lambda: model.init_params(cfg, jax.random.PRNGKey(0)))
    specs = shd.param_spec_tree(shapes, cfg, mesh, dp)
    assert all(s == P() or all(e is None for e in s)
               for s in jax.tree.leaves(
                   specs, is_leaf=lambda x: isinstance(x, P)))


def test_moe_and_ssm_spec_trees_cover_all_leaves():
    """Every family's tree gets a spec per leaf (structure mirrors)."""
    mesh = MeshStub(data=2, model=4)
    for arch in ("qwen3-moe-235b-a22b", "zamba2-1.2b", "xlstm-125m"):
        cfg = get_config(arch)
        shard = shd.make_shard_cfg(mesh, cfg, global_batch=8)
        shapes = jax.eval_shape(
            lambda: model.init_params(cfg, jax.random.PRNGKey(0)))
        specs = shd.param_spec_tree(shapes, cfg, mesh, shard)
        flat_p = jax.tree_util.tree_flatten_with_path(shapes)[0]
        flat_s = jax.tree.leaves(specs,
                                 is_leaf=lambda x: isinstance(x, P))
        assert len(flat_p) == len(flat_s)
        for (path, leaf), spec in zip(flat_p, flat_s):
            assert len(spec) <= len(leaf.shape), (path, spec)
            for dim, ax in zip(leaf.shape, tuple(spec) + (None,) * 99):
                if ax is not None:
                    sizes = [mesh.shape[a] for a in
                             (ax if isinstance(ax, tuple) else (ax,))]
                    assert dim % int(np.prod(sizes)) == 0, (path, spec)


def test_moe_experts_are_expert_parallel():
    mesh = MeshStub(data=2, model=4)
    cfg = get_config("qwen3-moe-235b-a22b")
    shard = shd.make_shard_cfg(mesh, cfg, global_batch=8)
    shapes = jax.eval_shape(
        lambda: model.init_params(cfg, jax.random.PRNGKey(0)))
    specs = shd.param_spec_tree(shapes, cfg, mesh, shard)
    experts = specs["stack"]["layers"]["ffn"]["experts"]
    assert experts["gate"][1] == "model"    # (L, E, d, f): E over tp
    assert experts["down"][1] == "model"


def test_named_on_single_device_mesh():
    """named() + device_put on the real 1-device mesh round-trips."""
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1, 1), ("data", "model"))
    cfg = get_config("llama3-8b")
    shard = shd.make_shard_cfg(mesh, cfg, global_batch=8)
    tree = {"w": jnp.ones((4, 8)), "norm": {"scale": jnp.ones((8,))}}
    specs = shd.param_spec_tree(tree, cfg, mesh, shard)
    placed = jax.device_put(tree, shd.named(specs, mesh))
    np.testing.assert_array_equal(np.asarray(placed["w"]),
                                  np.ones((4, 8)))


def test_path_str_matches_decay_filter_contract():
    from repro.optim.adamw import AdamW

    tree = {"stack": {"layers": {"ffn": {"down": {"w": 0, "b": 0}},
                                 "ln1": {"scale": 0},
                                 "mamba": {"A_log": 0, "dt_bias": 0}}},
            "embed": {"table": 0}}
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    paths = {shd._path_str(p) for p, _ in flat}
    assert "stack/layers/ffn/down/w" in paths
    f = AdamW().decay_filter
    decayed = {p for p in paths if f(p)}
    assert decayed == {"stack/layers/ffn/down/w", "embed/table"}


# ---------------------------------------------------------------------------
# compression properties
# ---------------------------------------------------------------------------
@settings(max_examples=30, deadline=None)
@given(n=st.integers(1, 4096), logmag=st.floats(-5.0, 4.0),
       seed=st.integers(0, 2 ** 16), onesided=st.booleans())
def test_quantize_roundtrip_property(n, logmag, seed, onesided):
    g = jax.random.normal(jax.random.PRNGKey(seed), (n,)) * (10.0 ** logmag)
    if onesided:
        g = jnp.abs(g)
    q, scale, err = quantize_int8(g)
    assert q.dtype == jnp.int8
    amax = float(jnp.max(jnp.abs(g)))
    # exact reconstruction: deq + err == g to fp32 rounding
    deq = dequantize_int8(q, scale, g.shape)
    np.testing.assert_allclose(np.asarray(deq + err), np.asarray(g),
                               rtol=0, atol=max(1e-12, amax * 1e-6))
    # quantization error is at most half a step per element
    assert float(jnp.max(jnp.abs(err))) <= float(scale) * 0.5 * (1 + 1e-5)


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 2 ** 16), t=st.integers(1, 8))
def test_error_feedback_telescopes(seed, t):
    """EF invariant: sum of applied (dequantized) updates equals the sum
    of true gradients minus the final residual — nothing is ever lost."""
    key = jax.random.PRNGKey(seed)
    gs = jax.random.normal(key, (t, 256))
    err = jnp.zeros((256,))
    applied = jnp.zeros((256,))
    for i in range(t):
        comp = gs[i] + err
        q, scale, err = quantize_int8(comp)
        applied = applied + dequantize_int8(q, scale, comp.shape)
        # residual stays one quantization step: EF never accumulates
        assert float(jnp.max(jnp.abs(err))) <= float(scale) * 0.5 * (1 + 1e-5)
    np.testing.assert_allclose(np.asarray(applied + err),
                               np.asarray(gs.sum(0)), rtol=0, atol=1e-4)


def test_zero_gradient_quantizes_to_zero():
    q, scale, err = quantize_int8(jnp.zeros((64,)))
    assert float(jnp.abs(q.astype(jnp.float32)).max()) == 0.0
    assert float(jnp.abs(err).max()) == 0.0
    assert np.isfinite(float(scale))


def test_ef_allreduce_single_device_mesh():
    """ef_allreduce_mean on a 1-device 'pod' axis == plain quantize."""
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1,), ("pod",))
    g = jax.random.normal(jax.random.PRNGKey(0), (128,))
    err0 = jnp.zeros((128,))
    fn = jax.shard_map(
        lambda g_, e_: ef_allreduce_mean(g_, e_, "pod"), mesh=mesh,
        in_specs=(P(), P()), out_specs=(P(), P()), check_vma=False)
    gm, ne = fn(g, err0)
    np.testing.assert_allclose(np.asarray(gm + ne), np.asarray(g),
                               rtol=0, atol=1e-5)


def test_wire_bytes_model():
    assert wire_bytes(1000, compressed=True) == 1004
    assert wire_bytes(1000, compressed=False) == 4000


# ---------------------------------------------------------------------------
# halo / BC properties (single-shard exchange_pad path — pure rules, no mesh)
# ---------------------------------------------------------------------------
# The slots x shards step trusts exchange_pad for every ghost zone, so the
# farm's correctness reduces to these rules: any halo width >= the stencil
# radius round-trips (the interior is untouched), ghost strips obey the BC
# rule exactly, and an impossible width fails loudly.
from repro.core.halo import (  # noqa: E402
    AxisSpec, bc_dirichlet, bc_mirror, bc_neumann, exchange_pad,
)

_BC_FACTORIES = {
    "dirichlet": lambda: bc_dirichlet(3.5),
    "neumann": bc_neumann,
    "mirror": lambda: bc_mirror(-1.0),
}


def _field(n, seed):
    rng = np.random.RandomState(seed)
    return jnp.asarray(rng.randn(n, n, n).astype(np.float32))


def _specs(bc_name, periodic=False):
    mk = _BC_FACTORIES[bc_name]
    return tuple(AxisSpec(a, periodic=periodic, bc_lo=mk(), bc_hi=mk())
                 for a in range(3))


@settings(max_examples=25, deadline=None)
@given(w=st.integers(1, 3), n=st.integers(4, 8), seed=st.integers(0, 999),
       bc=st.sampled_from(sorted(_BC_FACTORIES)))
def test_exchange_pad_roundtrips_interior_property(w, n, seed, bc):
    """Padding never rewrites the interior: cropping the ghosts back off
    recovers the original field bitwise, for every BC rule and any halo
    width >= the stencil radius (the width the kernels will ask for)."""
    u = _field(n, seed)
    padded = exchange_pad(u, (w, w, w), _specs(bc))
    assert padded.shape == (n + 2 * w,) * 3
    crop = padded[w:-w, w:-w, w:-w]
    np.testing.assert_array_equal(np.asarray(crop), np.asarray(u))


@settings(max_examples=25, deadline=None)
@given(wlo=st.integers(0, 3), whi=st.integers(0, 3), seed=st.integers(0, 999),
       bc=st.sampled_from(sorted(_BC_FACTORIES)))
def test_exchange_pad_one_sided_widths_property(wlo, whi, seed, bc):
    """(lo, hi) one-sided widths (upwind/staggered stencils) round-trip
    the same way."""
    n = 6
    u = _field(n, seed)
    padded = exchange_pad(u, ((wlo, whi),) * 3, _specs(bc))
    assert padded.shape == (n + wlo + whi,) * 3
    crop = padded[wlo:n + wlo, wlo:n + wlo, wlo:n + wlo]
    np.testing.assert_array_equal(np.asarray(crop), np.asarray(u))


@settings(max_examples=25, deadline=None)
@given(w=st.integers(1, 3), seed=st.integers(0, 999),
       axis=st.integers(0, 2), bc=st.sampled_from(sorted(_BC_FACTORIES)))
def test_exchange_pad_ghosts_obey_bc_rule_property(w, seed, axis, bc):
    """Ghost strips are exactly what the BC rule defines: dirichlet fills
    the value, neumann mirrors the adjacent interior, mirror flips the
    sign of the mirrored interior — on both the lo and hi side."""
    n = 6
    u = _field(n, seed)
    widths = [0, 0, 0]
    widths[axis] = w
    padded = np.asarray(exchange_pad(u, tuple(widths), _specs(bc)))
    un = np.asarray(u)
    lo = np.take(padded, range(0, w), axis=axis)
    hi = np.take(padded, range(n + w, n + 2 * w), axis=axis)
    near_lo = np.take(un, range(0, w), axis=axis)
    near_hi = np.take(un, range(n - w, n), axis=axis)
    if bc == "dirichlet":
        np.testing.assert_array_equal(lo, np.full_like(lo, 3.5))
        np.testing.assert_array_equal(hi, np.full_like(hi, 3.5))
    elif bc == "neumann":
        np.testing.assert_array_equal(lo, np.flip(near_lo, axis=axis))
        np.testing.assert_array_equal(hi, np.flip(near_hi, axis=axis))
    else:  # mirror(-1)
        np.testing.assert_array_equal(lo, -np.flip(near_lo, axis=axis))
        np.testing.assert_array_equal(hi, -np.flip(near_hi, axis=axis))


@settings(max_examples=25, deadline=None)
@given(w=st.integers(1, 3), seed=st.integers(0, 999), axis=st.integers(0, 2))
def test_exchange_pad_periodic_wraps_property(w, seed, axis):
    """Periodic ghosts are the wrapped far-side strips (what the ppermute
    delivers on a real mesh, degenerated to one shard)."""
    n = 6
    u = _field(n, seed)
    widths = [0, 0, 0]
    widths[axis] = w
    specs = tuple(AxisSpec(a, periodic=True) for a in range(3))
    padded = np.asarray(exchange_pad(u, tuple(widths), specs))
    ref = np.asarray(jnp.pad(u, [(wa, wa) if a == axis else (0, 0)
                                 for a, wa in enumerate([w] * 3)],
                             mode="wrap"))
    np.testing.assert_array_equal(padded, ref)


@settings(max_examples=15)
@given(n=st.integers(2, 4), extra=st.integers(1, 3),
       bc=st.sampled_from(sorted(_BC_FACTORIES)))
def test_exchange_pad_width_beyond_extent_raises_property(n, extra, bc):
    """A halo wider than the local block cannot be served by one exchange
    hop — it must fail loudly, not wrap garbage."""
    u = _field(n, 0)
    w = n + extra
    with pytest.raises(ValueError, match="halo width"):
        exchange_pad(u, (w, w, w), _specs(bc))
