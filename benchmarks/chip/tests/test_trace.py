"""The trace reduction and the metric readers, on synthetic events."""
import pytest

import trace as tr


def synthetic():
    # window 0..100; two step runs (10..40, 50..90) with ops inside, one
    # collective, and host spans covering the idle gaps
    dev = tr.Device(
        ops=[("fusion.1", 10, 30), ("collective-permute-done.2", 30, 40),
             ("fusion.1", 50, 70), ("copy.3", 60, 90), ("fusion.9", 95, 120)],
        modules=[("jit_step(1)", 10, 40), ("jit_step(1)", 50, 90),
                 ("jit_other", 95, 120)])
    host = [("bench.window", 0, 100), ("bench.submit", 0, 12),
            ("bench.block", 38, 52), ("bench.idle", 89, 100)]
    return tr.Trace(devices={0: dev}, host=host)


def test_union_and_idle():
    s = tr.summarize(synthetic(), "jit_step")
    # busy: 10..40, 50..90, 95..100 -> 75 of 100
    assert s.window_ns == 100
    assert s.busy_ns == 75
    assert s.idle_share == pytest.approx(0.25)


def test_step_runs_and_gap():
    s = tr.summarize(synthetic(), "jit_step")
    assert s.step_executions == 2
    assert s.step_busy_ns == 70
    assert s.step_gap_idle_ns == [10]


def test_collective_and_top_ops():
    s = tr.summarize(synthetic(), "jit_step")
    assert s.collective_ns == 10
    names = [n for n, _ in s.top_ops]
    assert names[0] == "fusion.1"
    assert dict(s.top_ops)["fusion.1"] == pytest.approx(40e-9)


def test_idle_gaps_named_after_host_span():
    s = tr.summarize(synthetic(), "jit_step")
    assert s.idle_gaps == [("bench.submit", 1e-8), ("bench.block", 1e-8),
                           ("bench.idle", 5e-9)]


def test_no_device_reads_nothing():
    s = tr.summarize(tr.Trace(devices={}, host=[("bench.window", 0, 10)]),
                     None)
    assert s.idle_share is None
    assert s.step_busy_ns is None


def test_missing_window_span_raises():
    with pytest.raises(ValueError):
        tr.summarize(tr.Trace(devices={}, host=[]), None)


def test_metric_readers_on_synthetic_trace():
    import types

    import run

    summary = tr.summarize(synthetic(), "jit_step")
    cell = types.SimpleNamespace(
        chips=1, config={"jacobi_iters": 60},
        devices=[types.SimpleNamespace(device_kind="TPU v5 lite")])
    record = {"device_steps": 4, "cells_per_device_step": 256 ** 3}
    view = run.Reading(cell, record, summary)
    bench = run.load_json(run.ROOT / "BENCHMARK.json")
    read = {m["name"]: run.load_module(
        run.HERE / "metrics" / f"{m['name']}.py").read(view)
        for m in bench["per_layer"]}
    assert read["device_idle_pct.throughput"] == pytest.approx(25.0)
    assert read["device_idle_pct.served"] == pytest.approx(25.0)
    assert read["step_device_ms"] == pytest.approx(70 / 4 / 1e6)
    assert read["steps_per_dispatch.served"] == pytest.approx(2.0)
    assert read["host_gap_ms_per_dispatch.served"] == pytest.approx(1e-5)
    assert read["collective_ms_per_step"] == pytest.approx(10 / 4 / 1e6)
    least = 256 ** 3 * 44 / 819e9
    assert read["step_roofline"] == pytest.approx(
        100 * least / (70 / 4 / 1e9))


def test_metric_readers_read_nothing_without_a_device():
    import types

    import run

    summary = tr.summarize(tr.Trace(devices={}, host=[
        ("bench.window", 0, 10)]), "jit_step")
    view = run.Reading(types.SimpleNamespace(chips=1, config={}, devices=[]),
                       {"device_steps": 4}, summary)
    bench = run.load_json(run.ROOT / "BENCHMARK.json")
    for m in bench["per_layer"]:
        mod = run.load_module(run.HERE / "metrics" / f"{m['name']}.py")
        assert mod.read(view) is None, m["name"]
