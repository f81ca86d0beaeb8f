"""Halo counters of the decomposed step: the ``runtime.step`` span carries
the halo bytes and collective-permutes a device step moves, counted once at
``prepare`` from the decomposition plan; every collective-permute of the
compiled step sits in the ``halo_permute`` scope; the meshless step is
untouched by that scope."""
import contextlib
import json
import re

import jax
import pytest

from repro import api, obs
from repro.cfd.ns3d import CFDConfig
from repro.launch import hlo_cost
from repro.obs import perf
from tests.helpers import run_with_devices

N, SHARDS, ITERS = 16, 4, 8
# per device: one 16 x 16 float32 plane across x for each side of each
# exchange -- 3 velocity pads x 2 sides, 3 divergence pads, 2 per Jacobi
# sweep, 1 projection pad
PERMUTES = 2 * 3 + 3 + 2 * ITERS + 1
PLANE_BYTES = N * N * 4

DECOMPOSED = f"""
import json, re
import jax
from repro import api, obs

seen = []
real = obs.span
obs.span = lambda name, **counts: (seen.append([name, counts]),
                                   real(name, **counts))[1]
rt = api.runtime(n={N}, nz={N}, mesh_shape=({SHARDS},), mesh_axes=("shard",),
                 decomposition=((0, "shard"),))
pr = rt.prepare("taylor_green", jacobi_iters={ITERS})
jax.block_until_ready(pr.step(pr.step(pr.state)))
hlo = jax.jit(pr.step).lower(pr.state).compile().as_text()
permutes = [re.search(r'op_name="([^"]*)"', line).group(1)
            for line in hlo.splitlines()
            if re.search(r" collective-permute(-start)?\\(", line)]
print(json.dumps({{"spans": seen, "permutes": permutes}}))
"""


@pytest.fixture(scope="module")
def decomposed():
    out = run_with_devices(DECOMPOSED, n_devices=SHARDS, timeout=540)
    return json.loads(out.strip().splitlines()[-1])


@pytest.mark.multidevice
def test_decomposed_step_span_carries_the_halo_counts(decomposed):
    steps = [c for n, c in decomposed["spans"] if n == "runtime.step"]
    want = {"halo_bytes": PERMUTES * PLANE_BYTES, "halo_permutes": PERMUTES}
    # two steps run, then one more span while the step is lowered
    assert len(steps) == 3 and all(c == want for c in steps), steps
    cfg = CFDConfig(shape=(N, N, N), case="taylor_green",
                    jacobi_iters=ITERS, decomposition=((0, "shard"),))
    active = {0: "shard"}
    assert want == {
        "halo_bytes": perf.halo_bytes_per_step(cfg, active,
                                               {"shard": SHARDS}),
        "halo_permutes": perf.halo_permutes_per_step(cfg, active)}


@pytest.mark.multidevice
def test_every_collective_permute_is_in_the_halo_permute_scope(decomposed):
    permutes = decomposed["permutes"]
    assert permutes
    assert all("/exchange_pad/halo_permute/" in p for p in permutes), \
        permutes


def _program(hlo: str) -> str:
    """``hlo`` without its source locations, which hold the test's own
    frames: the instructions and each op's scope path are kept."""
    head, rest = hlo.split("\nFileNames\n", 1)
    return head + re.sub(r" stack_frame_id=\d+", "", rest[rest.index("\n%"):])


def test_meshless_step_is_untouched_by_the_scope(monkeypatch):
    def step_hlo():
        seen = []
        real = obs.span
        monkeypatch.setattr(obs, "span", lambda name, **counts: (
            seen.append((name, counts)), real(name, **counts))[1])
        pr = api.runtime(n=N, nz=N).prepare("taylor_green",
                                            jacobi_iters=ITERS)
        text = jax.jit(pr.step).lower(pr.state).compile().as_text()
        return _program(text), [c for n, c in seen if n == "runtime.step"]

    with_scope, spans = step_hlo()
    named_scope = jax.named_scope
    monkeypatch.setattr(jax, "named_scope", lambda name: (
        contextlib.nullcontext() if name == "halo_permute"
        else named_scope(name)))
    without_scope, _ = step_hlo()
    assert "halo_permute" not in with_scope
    assert with_scope == without_scope
    assert spans == [{}]


@pytest.mark.parametrize("fused_sweeps", [1, 2])
def test_permute_count_matches_the_lowered_step(fused_sweeps):
    """The trip-count-aware count of the collective-permutes of the
    decomposed step, lowered over an abstract mesh, is the analytic one."""
    cfg = CFDConfig(shape=(N, N, N), extent=1.0, case="cavity",
                    jacobi_iters=ITERS, fused_sweeps=fused_sweeps,
                    decomposition={0: "shard"})
    text, active = perf.decomposed_step_hlo(
        cfg, n_slots=2, mesh_axes=(("slot", 1), ("shard", 2)))
    cost, status, _ = hlo_cost.safe_analyze(text, 2)
    assert status == "ok"
    assert cost.collective_counts["collective-permute"] == \
        perf.halo_permutes_per_step(cfg, active)
