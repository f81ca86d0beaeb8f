#!/usr/bin/env python3
"""Read the numbers a cell's correctness check compares, over many seeds,
for the program and for its control, in one process.

    python3 benchmarks/chip/readings.py --workload tgv256-dns \\
        --side program --seeds 1,2,3 --seconds 5
    python3 benchmarks/chip/readings.py --workload tgv256-dns \\
        --side control --seeds 1,2,3

``program``: one run of the cell per seed through the harness's own
``run_cell`` (set-up, a window of ``--seconds``, the check), at the cell's
own size.  ``control``: the cell's driver's ``control(cell)``, the
reference computed in the precision below the configuration's put in the
program's place and compared as the check compares.  Each seed prints one
JSON line with every number beside its limit.  A limit lies above the
program's readings and below the control's.  The benchmark's own runs
never run this.
"""
from __future__ import annotations

import argparse
import json
import sys

import run as harness


def program(workload: str, seed: int, seconds: float) -> dict:
    out, _ = harness.run_cell(workload, seed, seconds, False)
    return {"correct": out["correct"], "failed": out["failed"],
            "checks": out["checks"], "metrics": out["metrics"],
            "memory_peak_bytes": out["device"]["memory_peak_bytes"]}


def control(workload: str, seed: int, seconds: float) -> dict:
    bench = harness.load_json(harness.ROOT / "BENCHMARK.json")
    w, config, traffic = harness.cell_files(bench, workload)
    driver = harness.load_module(harness.HERE / "drivers"
                                 / f"{traffic['driver']}.py")
    devices = harness.chips_for(int(w["chips"]), require_tpu=True)
    cell = harness.Cell(w, config, traffic, seed, seconds, False, devices)
    checks = driver.control(cell)
    return {"rejected": any(v > limit for _, v, limit in checks),
            "checks": {n: {"value": v, "limit": limit}
                       for n, v, limit in checks}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--side", choices=("program", "control"), required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(harness.ROOT / "src"))
    harness.use_compile_cache()
    read = program if args.side == "program" else control
    for seed in (int(s) for s in args.seeds.split(",")):
        row = read(args.workload, seed, args.seconds)
        print(json.dumps({"workload": args.workload, "side": args.side,
                          "seed": seed, **row}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
