"""Substrate-layer tests: data pipeline, checkpointer (incl. kill-resume),
watchdog, optimizer, sharding rules, serving engine, compression."""
import dataclasses
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config, smoke
from repro.data.pipeline import DataConfig, PackedLMDataset, Prefetcher
from repro.ft.watchdog import Heartbeat, StepWatchdog
from repro.models import model
from repro.optim.adamw import AdamW, global_norm
from repro.optim.schedules import warmup_cosine

from tests.helpers import run_with_devices


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------
def test_data_deterministic_and_sharded():
    cfg = DataConfig(seed=7, vocab_size=997, seq_len=64, global_batch=8)
    ds = PackedLMDataset(cfg)
    b1 = ds.batch(3)
    b2 = ds.batch(3)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    # shard slices tile the global batch exactly
    s0 = ds.batch(3, shard_idx=0, num_shards=2)
    s1 = ds.batch(3, shard_idx=1, num_shards=2)
    np.testing.assert_array_equal(
        np.concatenate([s0["tokens"], s1["tokens"]]), b1["tokens"])
    assert b1["tokens"].dtype == np.int32
    assert (b1["tokens"] < cfg.vocab_size).all()
    # document-boundary masking exists
    assert (b1["targets"] == -1).sum() >= 0


def test_data_stream_has_structure():
    """Consecutive tokens carry mutual information — the stream is
    learnable (convergence tests need signal).  Structure is conditional
    (per-Markov-state Zipf), so bigram MI is the right probe."""
    v = 64
    cfg = DataConfig(seed=0, vocab_size=v, seq_len=512, global_batch=8,
                     n_states=8)
    ds = PackedLMDataset(cfg)
    toks = np.concatenate([ds.batch(i)["tokens"].reshape(-1)
                           for i in range(8)])
    joint = np.zeros((v, v))
    np.add.at(joint, (toks[:-1], toks[1:]), 1.0)
    joint /= joint.sum()
    px = joint.sum(1, keepdims=True)
    py = joint.sum(0, keepdims=True)
    nz = joint > 0
    mi = (joint[nz] * np.log(joint[nz] / (px @ py)[nz])).sum()
    assert mi > 0.2, mi  # nats; ~0 for an i.i.d. stream


def test_prefetcher():
    cfg = DataConfig(seed=1, vocab_size=128, seq_len=32, global_batch=2)
    ds = PackedLMDataset(cfg)
    it = Prefetcher(ds.iterate(), depth=2)
    a = next(it)
    b = next(it)
    assert a["tokens"].shape == (2, 32)
    assert not np.array_equal(a["tokens"], b["tokens"])
    it.close()


# ---------------------------------------------------------------------------
# checkpointer
# ---------------------------------------------------------------------------
def _tree(key):
    k1, k2 = jax.random.split(key)
    return {"a": jax.random.normal(k1, (4, 8)),
            "nested": {"b": jax.random.normal(k2, (3,)),
                       "step": jnp.ones((), jnp.int32) * 7}}


def test_checkpoint_roundtrip_and_retention(tmp_path):
    from repro.ckpt.checkpointer import Checkpointer

    ck = Checkpointer(str(tmp_path), keep_last=2)
    t0 = _tree(jax.random.PRNGKey(0))
    for s in (10, 20, 30):
        ck.save(s, t0)
    assert ck.steps() == [20, 30]  # retention pruned step 10
    restored = ck.restore(30, jax.tree.map(jnp.zeros_like, t0))
    for x, y in zip(jax.tree.leaves(t0), jax.tree.leaves(restored)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y))


def test_checkpoint_async_and_cleanup(tmp_path):
    from repro.ckpt.checkpointer import Checkpointer

    ck = Checkpointer(str(tmp_path))
    ck.save_async(5, _tree(jax.random.PRNGKey(1)))
    ck.wait()
    assert ck.latest_step() == 5
    # interrupted write debris is removed
    os.makedirs(os.path.join(str(tmp_path), "step_00000009.tmp-dead"))
    ck.cleanup()
    assert not any(".tmp-" in n for n in os.listdir(str(tmp_path)))


def test_checkpoint_shape_mismatch_raises(tmp_path):
    from repro.ckpt.checkpointer import Checkpointer

    ck = Checkpointer(str(tmp_path))
    ck.save(1, {"a": jnp.zeros((4,))})
    with pytest.raises(ValueError):
        ck.restore(1, {"a": jnp.zeros((5,))})


def test_checkpoint_single_sharding_broadcasts(tmp_path):
    """A lone Sharding broadcasts to every leaf (the simulation farm
    scatters one slot's fields this way); a mis-sized shardings tree is
    an error, never a silent zip-truncation that restores one leaf."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.ckpt.checkpointer import Checkpointer
    from repro.launch.mesh import make_mesh

    ck = Checkpointer(str(tmp_path))
    tree = {"a": jnp.arange(4.0), "b": {"c": jnp.arange(6.0)}}
    ck.save(1, tree)
    sh = NamedSharding(make_mesh((1,), ("shard",)), P())
    restored = ck.restore(1, jax.tree.map(jnp.zeros_like, tree),
                          shardings=sh)
    for x, y in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        assert y.sharding == sh
    with pytest.raises(ValueError, match="shardings has 1"):
        ck.restore(1, jax.tree.map(jnp.zeros_like, tree),
                   shardings={"a": sh})


def test_kill_resume_end_to_end(tmp_path):
    """Kill a training run mid-flight; resume must continue from the last
    checkpoint with identical data order (the node-failure drill)."""
    env = dict(os.environ,
               PYTHONPATH=os.path.join(os.path.dirname(os.path.dirname(
                   os.path.abspath(__file__))), "src"))
    args = [sys.executable, "-m", "repro.launch.train", "--arch",
            "llama3-8b", "--smoke", "--batch", "2", "--seq", "64",
            "--ckpt-dir", str(tmp_path), "--ckpt-every", "5",
            "--log-every", "1"]
    # phase 1: run 12 steps (checkpoints at 5, 10)
    p1 = subprocess.run(args + ["--steps", "12"], env=env,
                        capture_output=True, text=True, timeout=600)
    assert p1.returncode == 0, p1.stderr[-2000:]
    # phase 2: "restart" to 15 steps -> resumes from step 10
    p2 = subprocess.run(args + ["--steps", "15"], env=env,
                        capture_output=True, text=True, timeout=600)
    assert p2.returncode == 0, p2.stderr[-2000:]
    assert "resumed from step 10" in p2.stdout, p2.stdout


# ---------------------------------------------------------------------------
# watchdog
# ---------------------------------------------------------------------------
def test_watchdog_flags_stragglers():
    wd = StepWatchdog(warmup_steps=3, slow_factor=1.5, hang_factor=5.0,
                      checkpoint_after_slow=2)
    for i in range(6):
        wd.observe(i, 1.0)
    ev = wd.observe(6, 2.0)          # 2x > 1.5x -> slow
    assert [e.kind for e in ev] == ["slow_step"]
    ev = wd.observe(7, 2.5)          # second consecutive -> ckpt request
    kinds = [e.kind for e in ev]
    assert "slow_step" in kinds and "checkpoint_requested" in kinds
    ev = wd.observe(8, 30.0)         # way past hang threshold
    assert [e.kind for e in ev] == ["hang"]
    assert wd.should_checkpoint


def test_heartbeat(tmp_path):
    hb = Heartbeat(str(tmp_path / "hb"), interval_s=0.0)
    hb.beat()
    assert Heartbeat.is_alive(str(tmp_path / "hb"), deadline_s=60)
    assert not Heartbeat.is_alive(str(tmp_path / "nope"), deadline_s=60)


# ---------------------------------------------------------------------------
# optimizer
# ---------------------------------------------------------------------------
def test_adamw_descends_quadratic():
    opt = AdamW(lr=0.1, weight_decay=0.0, clip_norm=None)
    params = {"w": jnp.array([3.0, -2.0])}
    state = opt.init(params)
    for _ in range(200):
        grads = {"w": 2 * params["w"]}
        params, state, _ = opt.update(grads, state, params)
    assert float(jnp.abs(params["w"]).max()) < 0.05


def test_adamw_clip_and_bf16_moments():
    opt = AdamW(lr=1e-2, clip_norm=1.0, m_dtype=jnp.bfloat16,
                v_dtype=jnp.bfloat16)
    params = {"w": jnp.ones((8,))}
    state = opt.init(params)
    assert state.m["w"].dtype == jnp.bfloat16
    grads = {"w": jnp.full((8,), 100.0)}
    _, state2, stats = opt.update(grads, state, params)
    np.testing.assert_allclose(float(stats["clip_scale"]),
                               1.0 / float(global_norm(grads)), rtol=1e-5)


def test_warmup_cosine_schedule():
    lr = warmup_cosine(1.0, 10, 100, final_frac=0.1)
    assert float(lr(jnp.int32(0))) == 0.0
    assert abs(float(lr(jnp.int32(10))) - 1.0) < 1e-6
    assert float(lr(jnp.int32(100))) == pytest.approx(0.1, abs=1e-6)
    assert float(lr(jnp.int32(55))) < 1.0


# ---------------------------------------------------------------------------
# sharding rules
# ---------------------------------------------------------------------------
@pytest.mark.multidevice
def test_param_specs_divisibility_guard():
    """Rules only shard divisible dims (kv_heads=8 vs model=16 stays
    replicated; ff/vocab shard)."""
    script = """
import jax
from jax.sharding import PartitionSpec as P
from repro.configs.registry import get_config
from repro.dist import sharding as shd
from repro.launch.mesh import make_mesh
from repro.models import model

mesh = make_mesh((2, 4), ("data", "model"))
cfg = get_config("llama3-8b")
shard = shd.make_shard_cfg(mesh, cfg, global_batch=8)
shapes = jax.eval_shape(lambda: model.init_params(cfg, jax.random.PRNGKey(0)))
specs = shd.param_spec_tree(shapes, cfg, mesh, shard)
stack = specs["stack"]["layers"]
# wq (L, d, H=32, hd): heads shard over model=4
assert stack["attn"]["wq"] == P(None, "data", "model", None), stack["attn"]["wq"]
# wk (L, d, KH=8, hd): 8 % 4 == 0 -> sharded here
assert stack["attn"]["wk"] == P(None, "data", "model", None)
# mlp down (L, ff, d): TP on ff
assert stack["ffn"]["down"]["w"] == P(None, "model", "data")
# embedding (V, d): vocab-parallel
assert specs["embed"]["table"] == P("model", "data")
print("SPEC OK")
"""
    out = run_with_devices(script, n_devices=8)
    assert "SPEC OK" in out


@pytest.mark.multidevice
def test_cache_specs_seq_sharded():
    script = """
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.configs.registry import get_config
from repro.dist import sharding as shd
from repro.launch.mesh import make_mesh
from repro.models import model

mesh = make_mesh((2, 4), ("data", "model"))
cfg = get_config("llama3-8b")
shard = shd.make_shard_cfg(mesh, cfg, global_batch=8)
shapes = jax.eval_shape(lambda: model.init_caches(cfg, 8, 1024, jnp.bfloat16))
specs = shd.cache_spec_tree(shapes, cfg, mesh, shard)
# KV (L, B, S, KH, D): batch over data, SEQ over model (flash-decode)
assert specs.k == P(None, "data", "model", None, None), specs.k
print("CACHE OK")
"""
    out = run_with_devices(script, n_devices=8)
    assert "CACHE OK" in out


# ---------------------------------------------------------------------------
# serving engine
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("arch", ["llama3-8b", "zamba2-1.2b", "xlstm-125m"])
def test_engine_continuous_batching(arch):
    from repro.serve.engine import Request, ServingEngine

    cfg = smoke(get_config(arch))
    params = model.init_params(cfg, jax.random.PRNGKey(0))
    eng = ServingEngine(cfg, params, slots=2, max_seq=96)
    rng = np.random.default_rng(0)
    for rid in range(5):
        eng.submit(Request(
            rid, rng.integers(0, cfg.vocab_size,
                              size=int(rng.integers(4, 30))).astype(np.int32),
            max_new_tokens=6))
    done = eng.run_until_drained(max_steps=300)
    assert len(done) == 5
    assert all(len(r.output) == 6 for r in done)
    # slots were reused: 5 requests > 2 slots but steps < 5 * 6
    assert eng.steps < 30


def test_engine_matches_unbatched_decode():
    """Continuous-batching output == single-request greedy decode."""
    from repro.serve.engine import Request, ServingEngine

    cfg = smoke(get_config("llama3-8b"))
    params = model.init_params(cfg, jax.random.PRNGKey(3))
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
               for n in (5, 11, 19)]

    # reference: one-at-a-time greedy decode via prefill+decode_step
    def ref_decode(prompt, n_new):
        from repro.models.config import LOCAL
        caches = model.init_caches(cfg, 1, 96, jnp.float32)
        toks = jnp.asarray(prompt)[None]
        logits, caches = model.prefill(params, cfg, {"tokens": toks}, caches,
                                       LOCAL)
        out = []
        t = len(prompt)
        nxt = int(jnp.argmax(logits[0, -1]))
        out.append(nxt)
        for _ in range(n_new - 1):
            lg, caches = model.decode_step(
                params, cfg, jnp.asarray([[nxt]], jnp.int32), caches,
                jnp.int32(t), LOCAL)
            t += 1
            nxt = int(jnp.argmax(lg[0, -1]))
            out.append(nxt)
        return out

    eng = ServingEngine(cfg, params, slots=2, max_seq=96)
    for rid, p in enumerate(prompts):
        eng.submit(Request(rid, p, max_new_tokens=5))
    done = {r.rid: r.output for r in eng.run_until_drained(max_steps=100)}
    for rid, p in enumerate(prompts):
        assert done[rid] == ref_decode(p, 5), (rid, done[rid])


# ---------------------------------------------------------------------------
# gradient compression
# ---------------------------------------------------------------------------
def test_int8_quantize_error_feedback():
    from repro.dist.compression import dequantize_int8, quantize_int8

    g = jax.random.normal(jax.random.PRNGKey(0), (1000,)) * 3.0
    q, scale, err = quantize_int8(g)
    deq = dequantize_int8(q, scale, g.shape)
    np.testing.assert_allclose(np.asarray(deq + err), np.asarray(g),
                               rtol=0, atol=1e-5)
    # quantization error is small relative to signal
    rel = float(jnp.linalg.norm(err) / jnp.linalg.norm(g))
    assert rel < 0.02, rel


@pytest.mark.multidevice
def test_ef_allreduce_multidevice():
    script = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.dist.compression import ef_allreduce_mean
from repro.launch.mesh import make_mesh

mesh = make_mesh((4,), ("pod",))
g = jax.random.normal(jax.random.PRNGKey(0), (4, 512))  # per-pod grads
err = jnp.zeros((4, 512))

def local(g_l, e_l):
    gm, ne = ef_allreduce_mean(g_l[0], e_l[0], "pod")
    return gm[None], ne[None]

fn = jax.shard_map(local, mesh=mesh, in_specs=(P("pod"), P("pod")),
                   out_specs=(P("pod"), P("pod")), check_vma=False)
gm, ne = fn(g, err)
exact = g.mean(0)
# every pod sees (approximately) the mean; EF bounds the residual
for i in range(4):
    rel = float(jnp.linalg.norm(gm[i] - exact) / jnp.linalg.norm(exact))
    assert rel < 0.05, rel
print("EF OK")
"""
    out = run_with_devices(script, n_devices=4)
    assert "EF OK" in out


@pytest.mark.multidevice
def test_checkpoint_elastic_reshard():
    """Save from one mesh, restore onto a DIFFERENT mesh/sharding (the
    N->M elastic restart): values must round-trip exactly."""
    script = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.ckpt.checkpointer import Checkpointer
from repro.launch.mesh import make_mesh
import tempfile, os

tmp = tempfile.mkdtemp()
mesh_a = make_mesh((2, 4), ("data", "model"))
mesh_b = make_mesh((4, 2), ("data", "model"))
x = jax.random.normal(jax.random.PRNGKey(0), (16, 32))
tree = {"w": jax.device_put(x, NamedSharding(mesh_a, P("data", "model"))),
        "b": jax.device_put(jnp.arange(8.0),
                            NamedSharding(mesh_a, P("model")))}
ck = Checkpointer(tmp)
ck.save(3, tree)
target = jax.tree.map(jnp.zeros_like, tree)
shardings = {"w": NamedSharding(mesh_b, P("model", "data")),
             "b": NamedSharding(mesh_b, P("data"))}
restored = ck.restore(3, target, shardings)
np.testing.assert_array_equal(np.asarray(restored["w"]), np.asarray(x))
np.testing.assert_array_equal(np.asarray(restored["b"]),
                              np.arange(8.0, dtype=np.float32))
assert restored["w"].sharding.spec == P("model", "data")
print("ELASTIC OK")
"""
    out = run_with_devices(script, n_devices=8)
    assert "ELASTIC OK" in out
