"""Distribution substrate: compressed vs exact DP gradient all-reduce.

Two measurements, both on a forced-8-device host mesh (subprocess, like
the multi-device tests — the parent process must keep its 1-CPU view):

  1. allreduce microbench — ``ef_allreduce_mean`` (int8 + error feedback)
     vs exact fp32 ``pmean`` over a ``pod`` axis at several gradient
     sizes, reporting step time and the wire-byte model
     (``dist.compression.wire_bytes``: 1 B/elem + scale vs 4 B/elem).
  2. end-to-end — ``_make_dp_train_step`` exact vs
     ``compress_pod_grads=True`` on the smoke llama3-8b over a
     (pod, data, model) mesh: per-step wall time plus the loss/param
     deltas (the correctness margin the equivalence test pins at 5e-3).

On emulated host devices the "wire" is a memcpy, so int8's 4× byte saving
does NOT show up as time — the gate here is bytes + correctness; time
columns are for the roofline model and real-DCN extrapolation.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

_INNER = r"""
import json, time
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P

from repro.dist.compression import ef_allreduce_mean, wire_bytes
from repro.launch.mesh import make_mesh

QUICK = %(quick)r
sizes = [1 << 16, 1 << 20] if QUICK else [1 << 16, 1 << 20, 1 << 22]
reps = 5 if QUICK else 20
mesh = make_mesh((8,), ("pod",))
rows = []
for n in sizes:
    g = jax.random.normal(jax.random.PRNGKey(0), (8, n))
    err = jnp.zeros((8, n))

    def exact(g_l):
        return jax.lax.pmean(g_l, "pod")

    def comp(g_l, e_l):
        gm, ne = ef_allreduce_mean(g_l[0], e_l[0], "pod")
        return gm[None], ne[None]

    f_ex = jax.jit(jax.shard_map(exact, mesh=mesh, in_specs=P("pod"),
                                 out_specs=P("pod"), check_vma=False))
    f_cp = jax.jit(jax.shard_map(comp, mesh=mesh,
                                 in_specs=(P("pod"), P("pod")),
                                 out_specs=(P("pod"), P("pod")),
                                 check_vma=False))

    def bench(fn, *args):
        jax.block_until_ready(fn(*args))          # compile
        t0 = time.perf_counter()
        for _ in range(reps):
            out = fn(*args)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / reps

    t_ex = bench(f_ex, g)
    t_cp = bench(f_cp, g, err)
    gm, _ = f_cp(g, err)
    rel = float(jnp.linalg.norm(gm[0] - g.mean(0))
                / jnp.linalg.norm(g.mean(0)))
    rows.append({
        "n_elements": n,
        "exact_ms": round(t_ex * 1e3, 3),
        "compressed_ms": round(t_cp * 1e3, 3),
        "exact_wire_bytes": wire_bytes(n, compressed=False),
        "compressed_wire_bytes": wire_bytes(n, compressed=True),
        "mean_rel_err": rel,
    })

# -- end-to-end smoke train step -------------------------------------------
from repro.configs.registry import get_config, smoke
from repro.dist import sharding as shd
from repro.models import model
from repro.optim.adamw import AdamW
from repro.train import step as step_lib

cfg = smoke(get_config("llama3-8b"))
key = jax.random.PRNGKey(0)
params = model.init_params(cfg, key)
B, S = 8, 32
batch = {"tokens": jax.random.randint(key, (B, S), 0, cfg.vocab_size),
         "targets": jax.random.randint(jax.random.fold_in(key, 1), (B, S),
                                       0, cfg.vocab_size)}
mesh3 = make_mesh((2, 2, 2), ("pod", "data", "model"))
shard = shd.make_shard_cfg(mesh3, cfg, global_batch=B, mode="dp")
opt = AdamW(lr=1e-3)
step_reps = 3 if QUICK else 10
steps = {}
outs = {}
st0 = opt.init(params)
for name, kw in (("exact", {}), ("compressed", {"compress_pod_grads": True})):
    fn = jax.jit(step_lib._make_dp_train_step(cfg, shard, opt, **kw))
    p, st, m = fn(params, st0, batch)                   # compile + step 1
    jax.block_until_ready(p)
    t0 = time.perf_counter()
    for _ in range(step_reps):
        p2, st2, m2 = fn(params, st0, batch)
    jax.block_until_ready(p2)
    steps[name] = round((time.perf_counter() - t0) / step_reps * 1e3, 2)
    outs[name] = (p, float(m["loss"]))

dloss = abs(outs["exact"][1] - outs["compressed"][1])
dparam = max(float(jnp.abs(a.astype(jnp.float32)
                           - b.astype(jnp.float32)).max())
             for a, b in zip(jax.tree.leaves(outs["exact"][0]),
                             jax.tree.leaves(outs["compressed"][0])))
grad_elems = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
print("RESULT " + json.dumps({
    "allreduce": rows,
    "train_step_ms": steps,
    "train_loss_delta": dloss,
    "train_param_delta": dparam,
    "train_grad_elements": grad_elems,
    "train_pod_wire_bytes": {
        "exact": wire_bytes(grad_elems, compressed=False),
        "compressed": wire_bytes(grad_elems, compressed=True)},
}))
"""


def run(quick: bool = False) -> dict:
    t0 = time.time()
    env = dict(os.environ)
    # strip any inherited device-count flag: the LAST duplicate wins in
    # XLA's parser, so appending ours first would let the environment
    # override the required 8 (same fix as tests/helpers.run_with_devices)
    inherited = [f for f in env.get("XLA_FLAGS", "").split()
                 if not f.startswith("--xla_force_host_platform_device_count")]
    env["XLA_FLAGS"] = " ".join(
        ["--xla_force_host_platform_device_count=8"] + inherited)
    # eight forced host devices are CPU devices: the child must never
    # reach for a chip this process may hold
    env["JAX_PLATFORMS"] = "cpu"
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = (os.path.join(repo, "src") + os.pathsep
                         + env.get("PYTHONPATH", ""))
    proc = subprocess.run([sys.executable, "-c", _INNER % {"quick": quick}],
                          env=env, capture_output=True, text=True,
                          timeout=1800)
    if proc.returncode != 0:
        return {"bench": "dist", "passed": False,
                "error": proc.stderr[-2000:]}
    line = [l for l in proc.stdout.splitlines()
            if l.startswith("RESULT ")][-1]
    res = json.loads(line[len("RESULT "):])
    ok = (all(r["compressed_wire_bytes"] * 3.9 <= r["exact_wire_bytes"]
              for r in res["allreduce"])
          and all(r["mean_rel_err"] < 0.05 for r in res["allreduce"])
          and res["train_loss_delta"] < 1e-4
          and res["train_param_delta"] < 5e-3)
    return {"bench": "dist", "passed": bool(ok),
            "wall_s": round(time.time() - t0, 1), **res}


if __name__ == "__main__":
    print(json.dumps(run(quick="--quick" in sys.argv), indent=1))
