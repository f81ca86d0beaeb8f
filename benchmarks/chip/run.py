#!/usr/bin/env python3
"""Run one benchmark cell once on the chips of this machine.

    python3 benchmarks/chip/run.py --workload tgv256-dns --seed 7 \\
        --seconds 30 --trace 0

Everything a cell is made of is found by name from ``BENCHMARK.json`` at
the root of the checkout:

* ``configs/<config>.json``: the deployment's sizes, its source and the
  limits of its correctness check;
* ``traffic/<traffic>.json``: the traffic mix, a driver kind and its
  parameters;
* ``drivers/<kind>.py``: ``setup(cell)``, ``window(run, seconds, span)``,
  ``release(run)`` and ``check(run)`` for that kind of traffic, and
  ``control(cell)``, the check's numbers for the reference in a lower
  precision (``readings.py`` reads it; a run never does);
* ``metrics/<metric>.py``: ``read(run)``, one per-layer metric, from the
  run's record and its reduced profiler trace.

``--trace 0`` prints the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics, read from a profiler trace of the measured window.
Either way the last line of standard output is one JSON object, and the
numbers the correctness check compared, each beside its limit, are the last
lines of standard error.  Without a TPU, or with fewer chips than the cell
asks for, nothing is printed on standard output and the exit code is 1.
"""
from __future__ import annotations

import time

PROCESS_START = time.time()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
# a fixed directory inside the checkout: the path is part of the cache key
CACHE_DIR = ROOT / ".jax_cache"


class NoChip(Exception):
    pass


class Cell:
    """What a driver is given: the cell's entries, files and run options."""

    def __init__(self, workload: dict, config: dict, traffic: dict,
                 seed: int, seconds: float, trace: bool, devices: list):
        self.name = workload["name"]
        self.chips = int(workload["chips"])
        self.config = config
        self.traffic = traffic
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.devices = devices

    def span(self, name: str):
        """A host span in the profiler's trace (only when tracing)."""
        if not self.trace:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    """The module in ``path``, loaded once (names may hold dots)."""
    name = f"_bench_{path.parent.name}_{path.stem.replace('.', '_')}"
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return sys.modules[name]


def find(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r}")


def cell_files(bench: dict, workload: str) -> tuple[dict, dict, dict]:
    w = find(bench["workloads"], workload, "workload")
    config = load_json(HERE / "configs" / f"{w['config']}.json")
    traffic = load_json(HERE / "traffic" / f"{w['traffic']}.json")
    return w, config, traffic


def use_compile_cache():
    """JAX's persistent compilation cache in the checkout, for every
    program however small, so only a cell's first run compiles."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    # the TPU runtime's logs would go to a fixed /tmp path otherwise
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def chips_for(chips: int, require_tpu: bool) -> list:
    import jax

    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise NoChip(f"jax.devices()[0] is {devices[0].platform!r} "
                     f"{devices[0].device_kind!r}, not a TPU")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX has "
                     f"{len(devices)}")
    return devices[:chips]


def peak_bytes(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def metrics_for(bench: dict, workload: str, trace: bool) -> list[dict]:
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group
            if workload in m.get("workloads", [workload])]


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             require_tpu: bool = True, overrides: dict | None = None,
             process_start: float | None = None) -> tuple[dict, list]:
    """One run of one cell.  Returns the result line's object and the
    compared numbers as ``[(name, value, limit), ...]``.

    ``overrides`` replaces keys of the configuration (``"config"``) and of
    the traffic (``"traffic"``): the tests use it to run a cell at a tiny
    size on the CPU, with ``require_tpu=False``.
    """
    t_start = PROCESS_START if process_start is None else process_start
    if str(HERE) not in sys.path:
        sys.path.insert(0, str(HERE))
    bench = load_json(ROOT / "BENCHMARK.json")
    w, config, traffic = cell_files(bench, workload)
    overrides = overrides or {}
    config = {**config, **overrides.get("config", {})}
    traffic = {**traffic, **overrides.get("traffic", {})}
    driver = load_module(HERE / "drivers" / f"{traffic['driver']}.py")
    devices = chips_for(int(w["chips"]), require_tpu)
    cell = Cell(w, config, traffic, seed, seconds, trace, devices)

    import jax

    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **_: compiles.append(event)
        if event == "/jax/core/compile/backend_compile_duration" else None)

    run = driver.setup(cell)
    setup_s = time.time() - t_start
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    if trace:
        jax.profiler.start_trace(trace_dir)
    n_compiles = len(compiles)
    with cell.span("bench.window"):
        rec = driver.window(run, cell.seconds, cell.span)
    window_compiles = len(compiles) - n_compiles
    if trace:
        jax.profiler.stop_trace()
    rec["compiles_in_window"] = window_compiles
    memory = peak_bytes(devices)
    driver.release(run)
    gc.collect()
    checks = driver.check(run)
    # an answer that says the wrong thing fails the run; one that is late
    # counts in ``failed`` and in the latency, not here
    correct = all(v <= limit for _, v, limit in checks) and \
        rec.get("wrong", 0) == 0

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": memory}
    out = {"correct": bool(correct), "attempted": int(rec["attempted"]),
           "failed": int(rec.get("failed", 0)),
           "compiles_in_window": window_compiles}
    metrics = {}
    if trace:
        summary = load_module(HERE / "trace.py").reduce_dir(trace_dir, rec)
        shutil.rmtree(trace_dir, ignore_errors=True)
        view = Reading(cell, rec, summary)
        for m in metrics_for(bench, workload, trace=True):
            value = load_module(HERE / "metrics" / f"{m['name']}.py").read(view)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        out["breakdown"] = summary.breakdown()
        out["trace_modules"] = summary.modules
    else:
        e2e = {"setup_s": setup_s, **rec.get("end_to_end", {})}
        for m in metrics_for(bench, workload, trace=False):
            if m["name"] in e2e:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
    out["metrics"] = metrics
    out["device"] = device
    out["checks"] = {name: {"value": v, "limit": limit}
                     for name, v, limit in checks}
    return out, checks


class Reading:
    """What a per-layer metric reads: the cell, the window's record and
    the reduced trace."""

    def __init__(self, cell: Cell, record: dict, trace):
        self.cell = cell
        self.record = record
        self.trace = trace


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT / "src"))
    use_compile_cache()
    try:
        out, checks = run_cell(args.workload, args.seed, args.seconds,
                               bool(args.trace))
    except NoChip as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 1
    print(f"compiles in the window: {out['compiles_in_window']}",
          file=sys.stderr)
    if "trace_modules" in out:
        print(f"programs run in the traced window: {out.pop('trace_modules')}",
              file=sys.stderr)
    for name, value, limit in checks:
        print(f"check {name}: {value!r} (limit {limit!r})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
