#!/usr/bin/env python3
"""Record a small chip trace of the program's spans and stage scopes.

    python3 benchmarks/chip/tests/record_spans.py OUT_DIR

On a TPU, inside one ``bench.window`` span: three Taylor-Green steps at
32^3 through ``Runtime.prepare`` (``runtime.step`` spans, the step's five
stage scopes), then a cavity farm of 4 slots at 16 x 16 x 4 that runs two
waves of 4 members of 4 steps (``service.run``, ``farm.*`` and
``ensemble.*`` spans).  To keep the file under 2 MB a step makes 8 Jacobi
sweeps and the profiler does not trace Python calls (``run.py``'s traces
do); source paths in the ops' metadata are cut to file names.  Writes
the profiler's trace to ``OUT_DIR/spans_v5e.xplane.pb`` and prints, per plane, its lines, the
first event names of each, and the stats of the first events of the
device's op line and of each program span, to show where the trace keeps
what the reduction reads.
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
from repro.cfd.ns3d import params_from_config  # noqa: E402

SLOTS, WAVES, STEPS = 4, 2, 4
SWEEPS = 8


def record(tmp: str) -> None:
    jax.config.update("jax_hlo_source_file_canonicalization_regex", ".*/")
    pr = api.runtime(n=32, backend="jnp",
                     jacobi_iters=SWEEPS).prepare("taylor_green")
    rt = api.runtime(n=16, nz=4, n_slots=SLOTS, backend="jnp",
                     jacobi_iters=SWEEPS)
    for i in range(SLOTS * WAVES):
        rt.submit("cavity", re=100.0 + 100 * i, steps=STEPS, tag=str(i))
    (svc,) = rt.services()
    state = pr.state
    for _ in range(2):  # compile outside the trace
        state = pr.step(state)
    ex = svc.farm.exec
    ex.step_many(0)
    ex.read_slot(0)
    ex.write_slot(0, params_from_config(svc.farm.base_config))
    ex.clear_slot(0)
    jax.block_until_ready((state, ex.state))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=options)
    with TraceAnnotation("bench.window"):
        for _ in range(3):
            with TraceAnnotation("bench.step"):
                nxt = pr.step(state)
            with TraceAnnotation("bench.block"):
                jax.block_until_ready(state)
            state = nxt
        with TraceAnnotation("bench.block"):
            jax.block_until_ready(state)
        for _ in range(WAVES):
            with TraceAnnotation("bench.service_run"):
                svc.run(STEPS)
        with TraceAnnotation("bench.block"):
            jax.block_until_ready(ex.state)
    jax.profiler.stop_trace()
    assert len(svc.farm.results) == SLOTS * WAVES, svc.farm.results


def show(path: str) -> None:
    prefixes = ("bench.", "service.", "farm.", "ensemble.", "runtime.")
    for plane in ProfileData.from_file(path).planes:
        print("plane", repr(plane.name))
        for line in plane.lines:
            names, shown = [], 0
            for e in line.events:
                if e.name not in names and len(names) < 8:
                    names.append(e.name)
                if line.name == "XLA Ops" and shown < 6 or \
                        e.name.startswith(prefixes) and shown < 12:
                    print("     event", repr(e.name), dict(e.stats))
                    shown += 1
            print("   line", repr(line.name), names)


def main(out_dir: str) -> int:
    if jax.devices()[0].platform != "tpu":
        print("record_spans.py: no TPU", file=sys.stderr)
        return 1
    tmp = tempfile.mkdtemp(prefix="record_spans_")
    record(tmp)
    (path,) = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                        recursive=True)
    os.makedirs(out_dir, exist_ok=True)
    out = os.path.join(out_dir, "spans_v5e.xplane.pb")
    shutil.copy(path, out)
    shutil.rmtree(tmp, ignore_errors=True)
    print("wrote", out, os.path.getsize(out), "bytes")
    show(out)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
