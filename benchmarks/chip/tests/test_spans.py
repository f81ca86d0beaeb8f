"""The program-span reduction (``spans.py``) on a synthetic trace with
nested program spans, against hand-computed values, and beside
``trace.py``'s summary of the same events; and on a recorded chip
trace."""
from pathlib import Path

import pytest

import run
import spans

tr = spans.tr


def S(name, start, end, **stats):
    return spans.Span(name, start, end, stats)


RUN_K = "jit(run_k)/while/body"


def synthetic():
    # window 0..1000; the device is busy on 100..200 and 500..700 (two
    # runs of the step program) and 900..950 (another program), idle on
    # 0..100, 200..500, 700..900 and 950..1000
    ops = [(f"{RUN_K}/vmap({path})", s, e) for path, s, e in (
        ("update_velocity)/fusion.1", 100, 120),
        ("divergence)/fusion.2", 120, 130),
        ("jacobi)/while", 130, 180),
        ("jacobi)/while/body/closed_call/exchange_pad/pad", 130, 140),
        ("jacobi)/while/body/closed_call/fusion.3", 140, 180),
        ("project)/exchange_pad/concatenate", 180, 185),
        ("project)/fusion.4", 185, 200),
        ("update_velocity)/fusion.1", 500, 540),
        ("divergence)/fusion.2", 540, 560),
        ("jacobi)/while", 560, 660),
        ("jacobi)/while/body/closed_call/exchange_pad/pad", 560, 580),
        ("project)/exchange_pad/concatenate", 660, 670),
        ("project)/fusion.4", 660, 700),
        ("jacobian)/fusion.5", 690, 700))]
    ops += [("jit(run_k)/while", 100, 200), ("jit(run_k)/while", 500, 700),
            ("jit(write_slot)/dynamic_update_slice", 900, 950)]
    modules = [("jit_run_k(1)", 100, 200), ("jit_run_k(1)", 500, 700),
               ("jit_write_slot(2)", 900, 950)]
    host = [S("bench.window", 0, 1000), S("bench.service_run", 0, 1000),
            S("service.run", 10, 990),
            S("farm.admit", 10, 100),
            S("ensemble.write_slot", 20, 40),
            S("ensemble.write_slot", 50, 70),
            S("farm.step_chunk", 100, 110),
            S("farm.harvest", 200, 300),
            S("ensemble.read_slot", 210, 260, transfers=7, bytes=64),
            S("farm.harvest", 300, 400),
            S("ensemble.read_slot", 310, 350, transfers=7, bytes=64),
            S("farm.admit", 400, 500),
            S("ensemble.write_slot", 410, 450),
            S("farm.step_chunk", 500, 510),
            S("farm.harvest", 700, 800),
            S("ensemble.read_slot", 710, 760, transfers=7, bytes=64),
            # a read outside any harvest (a quarantine) does not count
            S("ensemble.read_slot", 850, 870, transfers=7, bytes=64)]
    return spans.Trace(devices={0: spans.Device(ops=ops, modules=modules)},
                       spans=host)


def as_trace_py(t):
    """The same events as ``trace.py`` keeps them."""
    return tr.Trace(
        devices={i: tr.Device(ops=list(d.ops), modules=list(d.modules))
                 for i, d in t.devices.items()},
        host=[(s.name, s.start, s.end) for s in t.spans
              if s.name.startswith("bench.")])


def test_idle_goes_to_the_innermost_span():
    r = spans.reduce(synthetic(), "run_k")
    assert r.window_ns == 1000 and r.idle_ns == 650
    assert r.idle_by_span == {
        "bench.service_run": 20, "service.run": 120, "farm.admit": 110,
        "ensemble.write_slot": 80, "farm.harvest": 160,
        "ensemble.read_slot": 160}
    assert sum(r.idle_by_span.values()) == r.idle_ns
    assert r.idle_gaps == [("farm.harvest", 3e-7), ("service.run", 2e-7),
                           ("farm.admit", 1e-7), ("service.run", 5e-8)]


def test_idle_inside_spans_counts_nested_spans():
    r = spans.reduce(synthetic(), "run_k")
    assert r.idle_in["farm.admit"] == 190
    assert r.idle_in["farm.harvest"] == 300
    assert r.idle_in["service.run"] == 630
    assert r.counts["ensemble.write_slot"] == 3
    assert r.counts["farm.harvest"] == 3


def test_readers_give_the_hand_computed_values():
    m = spans.reduce(synthetic(), "run_k").metrics(device_steps=5)
    assert m["admit_idle_ms_per_member"] == pytest.approx(190 / 3 / 1e6)
    assert m["harvest_idle_ms_per_member"] == pytest.approx(100 / 1e6)
    assert m["transfers_per_member"] == 7
    assert m["jacobi_device_ms"] == pytest.approx(150 / 5 / 1e6)
    assert m["stage_device_ms"] == pytest.approx({
        "update_velocity": 60 / 5e6, "divergence": 30 / 5e6,
        "jacobi": 150 / 5e6, "project": 60 / 5e6,
        "exchange_pad": 45 / 5e6})


def test_stage_match_takes_whole_scope_components():
    pat = spans.stage_pattern("jacobi")
    assert pat.search("jit(_step_local)/jacobi/while/body/add")
    assert pat.search("jit(run_k)/while/body/vmap(jacobi)/while")
    assert pat.search("jacobi")
    assert not pat.search("jit(run_k)/while/body/vmap(jacobian)/x")
    assert not pat.search("jit(jacobi_pressure)/add")


def test_nothing_to_read_without_a_device_or_a_span():
    t = synthetic()
    r = spans.reduce(spans.Trace(devices={}, spans=t.spans), "run_k")
    m = r.metrics(device_steps=5)
    assert m["admit_idle_ms_per_member"] is None
    assert m["harvest_idle_ms_per_member"] is None
    assert m["jacobi_device_ms"] is None
    # the host spans alone still count the harvest's copies
    assert m["transfers_per_member"] == 7
    bare = spans.Trace(devices=t.devices, spans=[S("bench.window", 0, 1000)])
    m = spans.reduce(bare, "run_k").metrics(device_steps=5)
    assert m["admit_idle_ms_per_member"] is None
    assert m["transfers_per_member"] is None
    with pytest.raises(ValueError):
        spans.reduce(spans.Trace(devices={}, spans=[]), None)


def test_the_same_idle_as_trace_py():
    t = synthetic()
    old = tr.summarize(as_trace_py(t), "run_k")
    new = spans.reduce(t, "run_k")
    assert new.window_ns == old.window_ns
    assert new.idle_ns == old.window_ns - old.busy_ns
    assert sorted(s for _, s in new.idle_gaps) == \
        sorted(s for _, s in old.idle_gaps)


def test_accepted_readers_read_what_they_read_before():
    """``spans.py`` leaves ``trace.py`` and the accepted readers alone: on
    this trace they read what they read without the program's spans."""
    import types

    t = synthetic()
    summary = tr.summarize(as_trace_py(t), "run_k")
    cell = types.SimpleNamespace(
        chips=1, config={"jacobi_iters": 40},
        devices=[types.SimpleNamespace(device_kind="TPU v5 lite")])
    view = run.Reading(cell, {"device_steps": 5,
                              "cells_per_device_step": 256 * 65536}, summary)
    bench = run.load_json(run.ROOT / "BENCHMARK.json")
    read = {m["name"]: run.load_module(
        run.HERE / "metrics" / f"{m['name']}.py").read(view)
        for m in bench["per_layer"]}
    assert read["device_idle_pct.throughput"] == pytest.approx(65.0)
    assert read["step_device_ms"] == pytest.approx(300 / 5 / 1e6)
    assert summary.step_executions == 2


def test_program_spans_match_the_reduction():
    from repro import obs

    assert all(name.startswith(spans.PREFIXES) for name in obs.SPANS)
    assert {"farm.admit", "farm.harvest", "ensemble.write_slot",
            "ensemble.read_slot"} <= set(obs.SPANS)


# -- the recorded chip trace (``record_spans.py`` on one TPU v5e) -------------
RECORDED = str(Path(__file__).resolve().parent / "data"
               / "spans_v5e.xplane.pb")
FARM_FIELD_BYTES = 7 * 16 * 16 * 4 * 4


@pytest.fixture(scope="module")
def recorded():
    return spans.load(RECORDED)


def test_recorded_planes_and_lines():
    from jax.profiler import ProfileData

    planes = {p.name: {line.name for line in p.lines}
              for p in ProfileData.from_file(RECORDED).planes}
    assert {tr.OPS_LINE, tr.MODULES_LINE} <= planes["/device:TPU:0"]
    assert any(name.startswith("/host:") for name in planes)


def test_recorded_program_spans_and_stats(recorded):
    counts = {}
    for s in recorded.spans:
        counts[s.name] = counts.get(s.name, 0) + 1
    assert counts["runtime.step"] == 3
    assert counts["service.run"] == 2 and counts["farm.admit"] == 2
    assert counts["ensemble.write_slot"] == 8
    assert counts["farm.harvest"] == counts["ensemble.read_slot"] == 8
    reads = [s.stats for s in recorded.spans
             if s.name == "ensemble.read_slot"]
    assert all(st == {"transfers": 7, "bytes": FARM_FIELD_BYTES}
               for st in reads)


def test_recorded_ops_carry_their_stage(recorded):
    (dev,) = recorded.devices.values()
    paths = [p for p, _, _ in dev.ops if p]
    for program in ("jit(_step_local)", "jit(run_k)"):
        mine = [p for p in paths if p.startswith(program)]
        for stage in spans.STAGES:
            pat = spans.stage_pattern(stage)
            assert any(pat.search(p) for p in mine), (program, stage)


def test_recorded_loads_what_trace_py_loads(recorded):
    old = tr.load(RECORDED)
    (dev,) = recorded.devices.values()
    (old_dev,) = old.devices.values()
    assert [(s, e) for _, s, e in dev.ops] == \
        [(s, e) for _, s, e in old_dev.ops]
    assert dev.modules == old_dev.modules
    assert sorted((s.name, s.start, s.end) for s in recorded.spans
                  if s.name.startswith("bench.")) == sorted(old.host)


def test_recorded_step_programs_found():
    old = tr.load(RECORDED)
    # the device clock runs a fraction of a millisecond behind the host's
    # here, so the first of the three DNS steps lands before the window
    assert tr.summarize(old, "_step_local").step_executions == 2
    assert tr.summarize(old, "run_k").step_executions == 2


def test_recorded_readings(recorded):
    r = spans.reduce(recorded, "run_k")
    m = r.metrics(device_steps=8)
    assert m["transfers_per_member"] == 7
    assert m["admit_idle_ms_per_member"] > 0
    assert m["harvest_idle_ms_per_member"] > 0
    assert m["jacobi_device_ms"] > 0
    assert sum(r.idle_by_span.values()) <= r.idle_ns
    farm = {"service.run", "farm.admit", "farm.harvest", "farm.step_chunk",
            "ensemble.write_slot", "ensemble.read_slot"}
    assert {label for label, _ in r.idle_gaps} <= farm | {"bench.block"}
    dns = spans.reduce(recorded, "_step_local").metrics(device_steps=3)
    assert dns["jacobi_device_ms"] > 0


def test_profile_runs_a_cell_on_the_cpu():
    """The CLI's path at a tiny size: the host spans count the harvest's
    copies; with no TPU in the trace no device reading is made."""
    import tiny

    out = spans.profile("ghia-sweep-backlog", tiny.SEED, 1.0,
                        require_tpu=False,
                        overrides=tiny.overrides("ghia-sweep-backlog"))
    assert out["program"]["transfers_per_member"] == 7
    assert out["program"]["jacobi_device_ms"] is None
    assert out["program"]["admit_idle_ms_per_member"] is None
    assert out["accepted"]["device_idle_pct.throughput"] is None
    assert out["end_to_end"]["cell_updates_per_s"] > 0
