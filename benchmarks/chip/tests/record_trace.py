#!/usr/bin/env python3
"""Record a small chip trace for the trace reduction's tests.

    python3 benchmarks/chip/tests/record_trace.py OUT_DIR

On a TPU: three Taylor-Green steps at 32^3 through ``Runtime.prepare``,
inside a ``bench.window`` span, each step's dispatch in ``bench.step`` and
each wait in ``bench.block``.  Copies the profiler's ``.xplane.pb`` to
``OUT_DIR/tg32_v5e.xplane.pb`` and prints, per plane, its lines and the
first event names of each, to show how the trace is laid out.
"""
import glob
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "..", "..", "..", "src"))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
from jax.profiler import ProfileData, TraceAnnotation  # noqa: E402

from repro import api  # noqa: E402


def main(out_dir: str) -> int:
    if jax.devices()[0].platform != "tpu":
        print("record_trace.py: no TPU", file=sys.stderr)
        return 1
    pr = api.runtime(n=32, backend="jnp").prepare("taylor_green")
    state = pr.state
    for _ in range(2):  # compile outside the trace
        state = pr.step(state)
    jax.block_until_ready(state)
    tmp = tempfile.mkdtemp(prefix="record_trace_")
    jax.profiler.start_trace(tmp)
    with TraceAnnotation("bench.window"):
        for _ in range(3):
            with TraceAnnotation("bench.step"):
                nxt = pr.step(state)
            with TraceAnnotation("bench.block"):
                jax.block_until_ready(state)
            state = nxt
        with TraceAnnotation("bench.block"):
            jax.block_until_ready(state)
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                        recursive=True)
    os.makedirs(out_dir, exist_ok=True)
    shutil.copy(path, os.path.join(out_dir, "tg32_v5e.xplane.pb"))
    shutil.rmtree(tmp, ignore_errors=True)
    for plane in ProfileData.from_file(path).planes:
        print("plane", repr(plane.name))
        for line in plane.lines:
            names = []
            for e in line.events:
                if e.name not in names:
                    names.append(e.name)
                if len(names) >= 8:
                    break
            print("   line", repr(line.name), names)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
