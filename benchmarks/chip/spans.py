#!/usr/bin/env python3
"""Put a traced window's device idle and device time down to the program.

``trace.py`` reduces a window to what the accepted per-layer metrics read:
device busy time, the step program's runs, the ten longest idle gaps named
after the benchmark's own ``bench.*`` spans.  This module reads the same
``.xplane.pb`` for what the program itself marks:

* its host spans (``repro.obs.SPANS``: ``service.*``, ``farm.*``,
  ``ensemble.*``, ``runtime.*``, ``schedule.*``) beside the ``bench.*``
  ones, with their stats (``ensemble.read_slot`` carries ``transfers`` and
  ``bytes``);
* each device operation's scope path, the ``jax.named_scope`` stack that
  XLA keeps in the op's metadata (the ``tf_op`` stat of the op's event
  metadata, e.g. ``jit(run_k)/while/body/vmap(jacobi)/while:``), so the
  step's time splits into its stages (``STAGES``).

``jax.profiler.ProfileData`` gives no event metadata, so ``load`` parses
the ``XSpace`` protobuf itself, from the fields of
``tsl/profiler/protobuf/xplane.proto`` that it reads.

What it reads, on the profiler's one clock, per device and then averaged
over the devices:

* idle by span: each idle instant of the window goes to the innermost
  host span over it; an idle gap is named after the span that holds most
  of it;
* ``admit_idle_ms_per_member``: idle time inside ``farm.admit`` spans
  (their nested spans included) over the ``ensemble.write_slot`` spans;
* ``harvest_idle_ms_per_member``: idle time inside ``farm.harvest`` spans
  over their count;
* ``transfers_per_member``: the ``transfers`` of the ``ensemble.read_slot``
  spans inside ``farm.harvest`` spans, over the ``farm.harvest`` spans;
* ``stage_device_ms``: per stage, the union of the intervals of the ops
  whose scope path holds the stage, inside the step program's runs, per
  device step; ``jacobi_device_ms`` is its ``jacobi`` entry.

A span counts in the window when it starts inside it.  The readings are
not in ``BENCHMARK.json``: ``run.py`` hands its metric readers only
``trace.py``'s summary.  Run a cell under the profiler and print them:

    python3 benchmarks/chip/spans.py --workload ghia-sweep-backlog \\
        --seed 7 --seconds 30 [--python-tracer 0]

The profiler traces Python calls, as in ``run.py``; ``--python-tracer 0``
turns that off, to see how much of the host's time the tracer itself
takes.
"""
from __future__ import annotations

import argparse
import dataclasses
import glob
import heapq
import json
import os
import re
import shutil
import sys
import tempfile
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
if str(HERE) not in sys.path:
    sys.path.insert(0, str(HERE))

import run  # noqa: E402

tr = run.load_module(HERE / "trace.py")

PREFIXES = ("bench.", "service.", "farm.", "ensemble.", "runtime.",
            "schedule.")
STAGES = ("update_velocity", "divergence", "jacobi", "project",
          "exchange_pad")
# the op's event-metadata stat that holds its scope path (XLA's op_name)
SCOPE_STAT = "tf_op"
NO_SPAN = "no span"


@dataclasses.dataclass
class Span:
    name: str
    start: float
    end: float
    stats: dict


@dataclasses.dataclass
class Device:
    ops: list          # [(scope path, start_ns, end_ns)]
    modules: list      # [(name, start_ns, end_ns)]


@dataclasses.dataclass
class Trace:
    devices: dict      # device id -> Device
    spans: list        # [Span], host spans named with PREFIXES


def _xspace_class():
    """The ``XSpace`` message, declared from the fields of
    ``tsl/profiler/protobuf/xplane.proto`` that ``load`` reads (a map is
    a repeated entry message on the wire)."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory

    F = descriptor_pb2.FieldDescriptorProto
    i64, u64, dbl, text = F.TYPE_INT64, F.TYPE_UINT64, F.TYPE_DOUBLE, \
        F.TYPE_STRING
    messages = {
        "XSpace": [("planes", 1, "XPlane")],
        "XPlane": [("name", 2, text), ("lines", 3, "XLine"),
                   ("event_metadata", 4, "EventMetadataEntry"),
                   ("stat_metadata", 5, "StatMetadataEntry")],
        "EventMetadataEntry": [("key", 1, i64),
                               ("value", 2, "XEventMetadata")],
        "StatMetadataEntry": [("key", 1, i64), ("value", 2, "XStatMetadata")],
        "XLine": [("name", 2, text), ("timestamp_ns", 3, i64),
                  ("events", 4, "XEvent")],
        "XEvent": [("metadata_id", 1, i64), ("offset_ps", 2, i64),
                   ("duration_ps", 3, i64), ("stats", 4, "XStat")],
        "XStat": [("metadata_id", 1, i64), ("double_value", 2, dbl),
                  ("uint64_value", 3, u64), ("int64_value", 4, i64),
                  ("str_value", 5, text), ("ref_value", 7, u64)],
        "XEventMetadata": [("id", 1, i64), ("name", 2, text),
                           ("stats", 5, "XStat")],
        "XStatMetadata": [("id", 1, i64), ("name", 2, text)],
    }
    repeated = {"planes", "lines", "event_metadata", "stat_metadata",
                "events", "stats"}
    fdp = descriptor_pb2.FileDescriptorProto(
        name="bench_xplane.proto", package="bench_xplane", syntax="proto2")
    for name, fields in messages.items():
        msg = fdp.message_type.add(name=name)
        for field, number, kind in fields:
            f = msg.field.add(name=field, number=number,
                              label=F.LABEL_REPEATED if field in repeated
                              else F.LABEL_OPTIONAL)
            if isinstance(kind, str):
                f.type, f.type_name = F.TYPE_MESSAGE, f".bench_xplane.{kind}"
            else:
                f.type = kind
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fdp)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xplane.XSpace"))


def _stats(stats, names: dict) -> dict:
    out = {}
    for st in stats:
        value = 0
        for field, v in st.ListFields():
            if field.name != "metadata_id":
                value = names.get(v, v) if field.name == "ref_value" else v
        out[names.get(st.metadata_id, st.metadata_id)] = value
    return out


def load(path: str) -> Trace:
    """The host spans and the scoped device ops of one ``.xplane.pb``
    file.  Times are whole nanoseconds, as
    ``jax.profiler.ProfileData`` gives them to ``trace.py``: the line's
    timestamp plus the event's offset, then its duration, each rounded
    down."""
    with open(path, "rb") as f:
        space = _xspace_class().FromString(f.read())
    devices, spans = {}, []
    for plane in space.planes:
        names = {e.key: e.value.name for e in plane.stat_metadata}
        meta = {e.key: e.value for e in plane.event_metadata}
        m = tr.DEVICE_PLANE.match(plane.name)
        if m:
            scope = {k: _stats(v.stats, names).get(SCOPE_STAT, "")
                     for k, v in meta.items()}
            dev = Device(ops=[], modules=[])
            for line in plane.lines:
                if line.name == tr.OPS_LINE:
                    dev.ops.extend(_times(line, lambda e: scope[e]))
                elif line.name == tr.MODULES_LINE:
                    dev.modules.extend(_times(line, lambda e: meta[e].name))
            devices[int(m.group(1))] = dev
        elif plane.name.startswith("/host:"):
            ours = {k for k, v in meta.items() if v.name.startswith(PREFIXES)}
            for line in plane.lines:
                for e in line.events:
                    if e.metadata_id in ours:
                        s = line.timestamp_ns + e.offset_ps // 1000
                        spans.append(Span(meta[e.metadata_id].name, s,
                                          s + e.duration_ps // 1000,
                                          _stats(e.stats, names)))
    return Trace(devices=devices, spans=spans)


def _times(line, label) -> list:
    """(label, start_ns, end_ns) of each event of ``line``."""
    out = []
    for e in line.events:
        s = line.timestamp_ns + e.offset_ps // 1000
        out.append((label(e.metadata_id), s, s + e.duration_ps // 1000))
    return out


def stage_pattern(stage: str) -> re.Pattern:
    """A scope path holds ``stage`` as one of its components, also when a
    transform wraps it: ``jit(run_k)/while/body/vmap(jacobi)/...``."""
    return re.compile(rf"(^|[/(]){re.escape(stage)}([)/:]|$)")


def _innermost_idle(spans: list, idle: np.ndarray, lo: float, hi: float):
    """Each idle instant in [lo, hi] goes to the innermost span over it:
    the one that started last.  Returns (idle ns per span index, idle ns
    per gap index per span name)."""
    edges = {lo, hi}
    for sp in spans:
        edges.update((min(max(sp.start, lo), hi), min(max(sp.end, lo), hi)))
    edges.update(idle.ravel().tolist())
    edges = sorted(edges)
    order = sorted(range(len(spans)), key=lambda i: spans[i].start)
    per_span: dict[int, float] = {}
    per_gap: dict[int, dict[str, float]] = {}
    heap, nxt, g = [], 0, 0
    for a, b in zip(edges, edges[1:]):
        while g < len(idle) and idle[g, 1] <= a:
            g += 1
        if g == len(idle) or idle[g, 0] >= b:
            continue                      # the device is busy on [a, b)
        while nxt < len(order) and spans[order[nxt]].start <= a:
            i = order[nxt]
            heapq.heappush(heap, (-spans[i].start, spans[i].end, i))
            nxt += 1
        while heap and heap[0][1] <= a:
            heapq.heappop(heap)
        # the top may hide an ended span further down; only the top counts
        i = heap[0][2] if heap else None
        name = spans[i].name if i is not None else NO_SPAN
        if i is not None:
            per_span[i] = per_span.get(i, 0.0) + (b - a)
        gap = per_gap.setdefault(g, {})
        gap[name] = gap.get(name, 0.0) + (b - a)
    return per_span, per_gap


@dataclasses.dataclass
class Readings:
    """What the program's spans and scopes say of one window, averaged
    over the devices."""

    window_ns: float
    n_devices: int
    idle_ns: float
    idle_by_span: dict         # span name -> idle ns (innermost)
    idle_gaps: list            # [(label, seconds)], longest first
    idle_in: dict              # span name -> idle ns inside its spans
    counts: dict               # span name -> spans starting in the window
    harvest_transfers: float   # read_slot transfers inside farm.harvest
    stage_ns: dict             # stage -> device ns inside the step runs

    def per_member(self, phase: str, per: str) -> float | None:
        n = self.counts.get(per, 0)
        if not self.n_devices or not n or phase not in self.idle_in:
            return None
        return self.idle_in[phase] / n / 1e6

    def metrics(self, device_steps: int | None) -> dict:
        harvests = self.counts.get("farm.harvest", 0)
        out = {
            "admit_idle_ms_per_member": self.per_member(
                "farm.admit", "ensemble.write_slot"),
            "harvest_idle_ms_per_member": self.per_member(
                "farm.harvest", "farm.harvest"),
            "transfers_per_member": (self.harvest_transfers / harvests
                                     if harvests else None),
            "jacobi_device_ms": None,
        }
        if device_steps and self.n_devices:
            ms = {s: t / device_steps / 1e6
                  for s, t in self.stage_ns.items()}
            out["jacobi_device_ms"] = ms["jacobi"] or None
            out["stage_device_ms"] = ms
        return out

    def breakdown(self) -> dict:
        by_span = sorted(self.idle_by_span.items(), key=lambda kv: -kv[1])
        return {"idle_s": self.idle_ns / 1e9,
                "idle_by_span": [[n, t / 1e9] for n, t in by_span],
                "idle_gaps": [[n, s] for n, s in self.idle_gaps]}


def reduce(trace: Trace, step_program: str | None) -> Readings:
    windows = [(s.start, s.end) for s in trace.spans
               if s.name == tr.WINDOW_SPAN]
    if not windows:
        raise ValueError(f"the trace has no {tr.WINDOW_SPAN!r} span")
    lo, hi = windows[0]
    spans = [s for s in trace.spans
             if s.name != tr.WINDOW_SPAN and s.end > lo and s.start < hi]
    inside = [s for s in spans if lo <= s.start < hi]
    counts: dict[str, int] = {}
    for s in inside:
        counts[s.name] = counts.get(s.name, 0) + 1
    harvests = tr.merge((s.start, s.end) for s in inside
                        if s.name == "farm.harvest")
    transfers = sum(s.stats.get("transfers", 0) for s in inside
                    if s.name == "ensemble.read_slot"
                    and _within(harvests, s.start, s.end))
    unions = {name: tr.clip(tr.merge((s.start, s.end) for s in spans
                                     if s.name == name), lo, hi)
              for name in {s.name for s in spans}}
    patterns = {st: stage_pattern(st) for st in STAGES}
    idle_tot, by_span, idle_in, gaps = [], {}, {}, []
    stage_ns = {st: [] for st in STAGES}
    for dev_id in sorted(trace.devices):
        dev = trace.devices[dev_id]
        busy = tr.merge((max(s, lo), min(e, hi)) for _, s, e in dev.ops
                        if e > lo and s < hi)
        idle = tr.complement(busy, lo, hi)
        idle_tot.append(tr.length(idle))
        for name, u in unions.items():
            idle_in[name] = idle_in.get(name, 0.0) + tr.intersect(idle, u)
        per_span, per_gap = _innermost_idle(spans, idle, lo, hi)
        for i, t in per_span.items():
            by_span[spans[i].name] = by_span.get(spans[i].name, 0.0) + t
        for g, names in per_gap.items():
            label = max(names, key=names.get)
            gaps.append((label, (idle[g, 1] - idle[g, 0]) / 1e9))
        runs = tr.clip(tr.merge((s, e) for n, s, e in dev.modules
                                if step_program and step_program in n),
                       lo, hi)
        for st, pat in patterns.items():
            ops = tr.merge((s, e) for path, s, e in dev.ops
                           if path and pat.search(path))
            stage_ns[st].append(tr.intersect(ops, runs))
    n = len(trace.devices)
    gaps.sort(key=lambda g: -g[1])
    return Readings(
        window_ns=hi - lo, n_devices=n,
        idle_ns=float(np.mean(idle_tot)) if n else 0.0,
        idle_by_span={k: v / n for k, v in by_span.items()} if n else {},
        idle_gaps=gaps[:tr.TOP],
        idle_in={k: v / n for k, v in idle_in.items()} if n else {},
        counts=counts, harvest_transfers=float(transfers),
        stage_ns={st: float(np.mean(v)) if v else 0.0
                  for st, v in stage_ns.items()})


def _within(union: np.ndarray, s: float, e: float) -> bool:
    """Does one interval of the disjoint sorted ``union`` hold [s, e]?"""
    if not len(union):
        return False
    i = int(np.searchsorted(union[:, 0], s, side="right")) - 1
    return i >= 0 and union[i, 0] <= s and e <= union[i, 1]


# -- running a cell under the profiler ----------------------------------------
def profile(workload: str, seed: int, seconds: float, *,
            python_tracer: bool = True,
            require_tpu: bool = True, overrides: dict | None = None) -> dict:
    """One traced window of ``workload``, as ``run.py --trace 1`` takes it
    (no correctness check): the accepted per-layer metrics, the window's
    own end-to-end numbers, and the program's readings."""
    import jax

    bench = run.load_json(run.ROOT / "BENCHMARK.json")
    w, config, traffic = run.cell_files(bench, workload)
    overrides = overrides or {}
    config = {**config, **overrides.get("config", {})}
    traffic = {**traffic, **overrides.get("traffic", {})}
    driver = run.load_module(HERE / "drivers" / f"{traffic['driver']}.py")
    devices = run.chips_for(int(w["chips"]), require_tpu)
    cell = run.Cell(w, config, traffic, seed, seconds, True, devices)
    state = driver.setup(cell)
    trace_dir = tempfile.mkdtemp(prefix="spans_trace_")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = int(python_tracer)
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    with cell.span(tr.WINDOW_SPAN):
        rec = driver.window(state, cell.seconds, cell.span)
    jax.profiler.stop_trace()
    driver.release(state)
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    summary = tr.summarize(tr.load(path), rec.get("step_program"))
    view = run.Reading(cell, rec, summary)
    accepted = {m["name"]: run.load_module(
        HERE / "metrics" / f"{m['name']}.py").read(view)
        for m in run.metrics_for(bench, workload, trace=True)}
    readings = reduce(load(path), rec.get("step_program"))
    shutil.rmtree(trace_dir, ignore_errors=True)
    return {"workload": workload, "seed": seed,
            "python_tracer": python_tracer,
            "end_to_end": rec.get("end_to_end", {}),
            "device_steps": rec.get("device_steps"),
            "accepted": accepted,
            "busy_s": summary.busy_s, "window_s": summary.window_s,
            "program": readings.metrics(rec.get("device_steps")),
            "breakdown": readings.breakdown()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--python-tracer", type=int, choices=(0, 1), default=1)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(run.ROOT / "src"))
    run.use_compile_cache()
    try:
        out = profile(args.workload, args.seed, args.seconds,
                      python_tracer=bool(args.python_tracer))
    except run.NoChip as e:
        print(f"spans.py: {e}", file=sys.stderr)
        return 1
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
