"""The halo bytes of a decomposed step and the ``halo_gb_per_s`` reader."""
import json
import types
from pathlib import Path

import pytest

import halo_work
import run

CONFIGS = Path(__file__).resolve().parents[1] / "configs"
X_SPLIT = {"shape": [4], "axes": ["shard"], "decomposition": [[0, "shard"]]}


def _program_bytes(config: dict) -> int:
    from repro.cfd.ns3d import CFDConfig
    from repro.obs import perf

    mesh = config["mesh"]
    cfg = CFDConfig(shape=tuple(config["grid"]), case="taylor_green",
                    jacobi_iters=config["jacobi_iters"],
                    decomposition=tuple(map(tuple, mesh["decomposition"])))
    active = {ax: name for ax, name in mesh["decomposition"]}
    return perf.halo_bytes_per_step(
        cfg, active, dict(zip(mesh["axes"], mesh["shape"])))


@pytest.mark.parametrize("name", ["tgv-dns-768-x4", "tgv-dns-768-x4-v2"])
def test_768_cubed_over_four_chips(name):
    config = json.loads((CONFIGS / f"{name}.json").read_text())
    # 130 planes of 768 x 768 float32 a device step: 2 x 3 velocity pads,
    # 3 one-sided divergence pads, 2 x 60 sweeps, 1 projection pad
    assert halo_work.step_bytes(config) == 130 * 768 * 768 * 4 \
        == 306_708_480 == _program_bytes(config)


def test_meshless_step_moves_nothing():
    config = json.loads((CONFIGS / "tgv-dns-256.json").read_text())
    assert halo_work.step_bytes(config) == 0


@pytest.mark.parametrize("axis", [0, 1])
def test_tiny_step_equals_the_lowered_step(axis):
    """At 16^3 over four shards, the bytes equal the collective-permute
    operand bytes of the lowered step times their loops' trip counts, and
    a split after x carries the padded earlier axes in its strips."""
    from repro.cfd.ns3d import CFDConfig
    from repro.launch import hlo_cost
    from repro.obs import perf

    config = {"grid": [16, 16, 16], "jacobi_iters": 8,
              "mesh": {**X_SPLIT, "decomposition": [[axis, "shard"]]}}
    cfg = CFDConfig(shape=(16, 16, 16), case="taylor_green",
                    jacobi_iters=8, decomposition={axis: "shard"})
    text, _ = perf.decomposed_step_hlo(
        cfg, n_slots=1, mesh_axes=(("slot", 1), ("shard", 4)))
    cost, status, _ = hlo_cost.safe_analyze(text, 4)
    assert status == "ok"
    lowered = cost.collective_bytes["collective-permute"]
    assert halo_work.step_bytes(config) == lowered == _program_bytes(config)


def _reading(config, collective_ns, steps=10):
    return run.Reading(types.SimpleNamespace(config=config),
                       {"device_steps": steps},
                       types.SimpleNamespace(collective_ns=collective_ns))


def test_halo_gb_per_s_reads_bytes_over_collective_time():
    reader = run.load_module(run.HERE / "metrics" / "halo_gb_per_s.py")
    config = {"grid": [768, 768, 768], "jacobi_iters": 60, "mesh": X_SPLIT}
    # 3.41 ms of collective-permutes a step over 10 steps
    assert reader.read(_reading(config, 34.1e6)) == \
        pytest.approx(306_708_480 / 3.41e-3 / 1e9)
    assert reader.read(_reading(config, 0.0)) is None
    assert reader.read(_reading(config, 34.1e6, steps=0)) is None
    meshless = {"grid": [256, 256, 256], "jacobi_iters": 60, "mesh": None}
    assert reader.read(_reading(meshless, 34.1e6)) is None
