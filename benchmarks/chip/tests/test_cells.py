"""Every cell's driver, its metric readers, the faults and the control, at
a tiny size on the CPU.  A CPU run prints no device metric."""
import json
import os
import subprocess
import sys

import jax.numpy as jnp
import pytest

import faults
import run
import tiny

HERE = os.path.dirname(os.path.abspath(__file__))
ONE_CHIP = ("tgv256-dns", "ghia-sweep-backlog", "ghia-served-poisson")
FAULTS = {
    "tgv256-dns": ("unchanged", "altered"),
    "ghia-sweep-backlog": ("unchanged", "half_batch", "altered"),
    "ghia-served-poisson": ("unchanged", "half_batch", "altered"),
    "tgv768-x4": ("unchanged", "no_exchange", "altered"),
}


def one_run(workload, trace=False, seconds=2.0):
    out, _ = run.run_cell(workload, tiny.SEED, seconds, trace,
                          require_tpu=False,
                          overrides=tiny.overrides(workload))
    return out


@pytest.fixture(autouse=True)
def fresh_farm_cache():
    from repro.sim import farm

    farm.reset_compile_cache()
    yield
    farm.reset_compile_cache()


@pytest.mark.parametrize("workload", ONE_CHIP)
def test_run_without_trace(workload):
    out = one_run(workload)
    bench = run.load_json(run.ROOT / "BENCHMARK.json")
    wanted = {m["name"] for m in run.metrics_for(bench, workload, False)}
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == wanted
    assert out["device"]["platform"] == "cpu"
    assert out["compiles_in_window"] == 0
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("workload", ONE_CHIP)
def test_traced_run_prints_no_device_metric_on_cpu(workload):
    out = one_run(workload, trace=True)
    assert out["correct"], out["checks"]
    assert out["metrics"] == {}
    assert out["device"]["busy_s"] == 0.0
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


@pytest.mark.parametrize("workload,fault", [
    (w, f) for w in ONE_CHIP for f in FAULTS[w]])
def test_fault_is_caught(workload, fault, monkeypatch):
    faults.plant(fault, monkeypatch.setattr)
    out = one_run(workload)
    assert not out["correct"], out["checks"]


def _x4(*args):
    proc = subprocess.run([sys.executable, os.path.join(HERE, "x4_case.py"),
                           *args], capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_x4_run_on_four_cpu_devices():
    out = _x4()
    assert out["correct"], out["checks"]


@pytest.mark.parametrize("fault", FAULTS["tgv768-x4"])
def test_x4_fault_is_caught(fault):
    out = _x4(fault)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("workload", ONE_CHIP)
def test_control_is_rejected(workload):
    bench = run.load_json(run.ROOT / "BENCHMARK.json")
    w, config, traffic = run.cell_files(bench, workload)
    ov = tiny.overrides(workload)
    config = {**config, **ov.get("config", {})}
    traffic = {**traffic, **ov.get("traffic", {})}
    import jax

    driver = run.load_module(run.HERE / "drivers"
                             / f"{traffic['driver']}.py")
    for seed in (1, 2, 3):
        cell = run.Cell(w, config, traffic, seed, 2.0, False,
                        jax.devices()[:1])
        checks = driver.control(cell)
        assert any(v > limit for _, v, limit in checks), (seed, checks)
