"""Paper Fig. 4 — scaling of the framework-built CFD code.

The paper shows near-linear speed-up of the CaCUDA CFD code to 12 GPUs
(weak scaling, domain grows with node count).  Without real hardware the
analogue is structural: dry-run the sharded step at 1/2/4/8 devices (weak
scaling: fixed per-device block), extract the roofline terms per device,
and report the modeled parallel efficiency

    eff(N) = T_model(1) / T_model(N),  T_model = max(compute, memory, coll)

where per-device compute/memory stay constant under weak scaling and the
halo-exchange collective grows with the surface — the same efficiency
shape as the paper's figure.  Runs in subprocesses (device count is
locked at jax init).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_SCRIPT = """
import sys, json
import jax
from repro.cfd.ns3d import CFDConfig, NavierStokes3D
from repro.launch.mesh import make_mesh
from repro.launch import hlo_cost
from repro.core.rooflinemodel import V5E, terms_from_counts

n_dev = int(sys.argv[1])
block = int(sys.argv[2])
mesh = make_mesh((n_dev,), ("data",)) if n_dev > 1 else None
cfg = CFDConfig(shape=(block * max(n_dev, 1), block, block),
                case="taylor_green", nu=1e-3, dt=1e-3, jacobi_iters=20,
                decomposition=((0, "data"),) if n_dev > 1 else ())
solver = NavierStokes3D(cfg, mesh)
state = solver.init_state()
step = solver.make_step()
lowered = jax.jit(step).lower(state)
compiled = lowered.compile()
cost = hlo_cost.analyze(compiled.as_text(), max(n_dev, 1))
terms = terms_from_counts(cost.flops, cost.bytes,
                          cost.collective_wire_bytes, dtype="fp32")
print("RESULT " + json.dumps({
    "n_dev": n_dev,
    "flops": cost.flops, "bytes": cost.bytes,
    "coll": cost.collective_wire_bytes,
    "compute_s": terms.compute_s, "memory_s": terms.memory_s,
    "collective_s": terms.collective_s,
    "t_model": terms.step_time_s}))
"""


def run(block: int = 32, devices=(1, 2, 4, 8), quick: bool = False) -> dict:
    if quick:
        block, devices = 24, (1, 2, 4)
    rows = []
    for n in devices:
        env = dict(os.environ)
        # forced host devices are CPU devices: the child must never reach
        # for a chip this process may hold
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={max(n,1)}"
        env["PYTHONPATH"] = os.path.join(REPO, "src")
        proc = subprocess.run(
            [sys.executable, "-c", _SCRIPT, str(n), str(block)],
            env=env, capture_output=True, text=True, timeout=900)
        if proc.returncode != 0:
            raise RuntimeError(proc.stderr[-2000:])
        line = [l for l in proc.stdout.splitlines()
                if l.startswith("RESULT ")][0]
        rows.append(json.loads(line[len("RESULT "):]))
    t1 = rows[0]["t_model"]
    for r in rows:
        r["efficiency"] = round(t1 / r["t_model"], 4)
        r["speedup"] = round(r["n_dev"] * t1 / r["t_model"], 3)
    return {
        "bench": "scaling_weak",
        "paper_analogue": "Fig. 4 (speed-up to 12 GPUs)",
        "per_device_block": f"{block}^3",
        "rows": rows,
        "passed": all(r["efficiency"] > 0.7 for r in rows),
    }


if __name__ == "__main__":
    print(json.dumps(run(), indent=1))
