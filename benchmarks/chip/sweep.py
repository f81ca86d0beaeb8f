#!/usr/bin/env python3
"""Find the arrival rate a served farm cell sustains, by a sweep.

    python3 benchmarks/chip/sweep.py --workload ghia-served-poisson \\
        --seconds 20 --factors 0.5,0.7,0.85,1.0,1.15

In one process and one farm: first the capacity, as members finished per
second while the queue never empties (the traffic's lengths, driven the
same way); then, for each factor, an open-loop window at that share of the
capacity, drained before the next.  Each prints its rate, the p95 latency
of its latency set, and how the queue grew over the second half of the
window.  The served cell's rate is fixed, by hand, at 0.8 of the highest
rate whose queue does not grow.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
import types

import numpy as np

import run as harness


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--factors", default="0.5,0.7,0.85,1.0,1.15")
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(harness.ROOT / "src"))
    harness.use_compile_cache()
    bench = harness.load_json(harness.ROOT / "BENCHMARK.json")
    w, cfg, tr = harness.cell_files(bench, args.workload)
    devices = harness.chips_for(int(w["chips"]), require_tpu=True)
    drv = harness.load_module(harness.HERE / "drivers" / f"{tr['driver']}.py")
    import farm_common

    cell = harness.Cell(w, cfg, tr, args.seed, args.seconds, False, devices)
    base = drv.setup(cell)
    rt, svc = base.rt, base.svc
    nospan = contextlib.nullcontext

    # capacity: a queue that never empties, the traffic's lengths
    times, steps, rng = drv.arrivals(tr, args.seconds, args.seed)
    res = farm_common.re_sequence(cfg, len(times), rng)
    n = 16 * cfg["slots"]
    lengths = np.resize(steps, n)
    for i in range(n):
        farm_common.submit(rt, cfg, res[i % len(res)], lengths[i],
                           tag=f"cap{i}")
    seen = len(svc.farm.results)
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < args.seconds:
        svc.run(tr["budget"])
    elapsed = time.perf_counter() - t0
    finished = len(svc.farm.results) - seen
    capacity = finished / elapsed
    print(json.dumps({"capacity_members_per_s": capacity,
                      "finished": finished, "seconds": elapsed,
                      "still_queued": svc.farm.table.n_queued}), flush=True)
    svc.drain()

    for f in (float(x) for x in args.factors.split(",")):
        rate = f * capacity
        traffic = {**tr, "rate_per_s": rate}
        times, steps, rng = drv.arrivals(traffic, args.seconds, args.seed)
        res = farm_common.re_sequence(cfg, len(times), rng)
        c = harness.Cell(w, cfg, traffic, args.seed, args.seconds, False,
                         devices)
        r = types.SimpleNamespace(cell=c, rt=rt, svc=svc, rng=rng,
                                  times=times, steps=steps, res=res,
                                  seen=len(svc.farm.results), finish={},
                                  status={})
        depth = []

        def span(name, r=r, depth=depth):
            depth.append((time.perf_counter(), len(r.times) and
                          svc.farm.table.n_queued))
            return nospan()

        t0 = time.perf_counter()
        rec = drv.window(r, args.seconds, span)
        mid = [d for t, d in depth if t >= t0 + args.seconds / 2]
        print(json.dumps({
            "factor": f, "rate_per_s": rate, "submitted": rec["submitted"],
            "p95_s": rec["end_to_end"]["member_latency_p95_s"],
            "missing": rec["missing"], "latency_set": rec["latency_set"],
            "queued_mid": mid[0] if mid else None,
            "queued_end": rec["queued_at_end"],
            "generator_lag_max_s": rec["generator_lag_max_s"]}), flush=True)
        svc.drain()
    return 0


if __name__ == "__main__":
    sys.exit(main())
