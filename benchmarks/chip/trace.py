"""Reduce a profiler trace of one measured window to what the metrics read.

The JAX profiler writes an ``.xplane.pb`` file; ``jax.profiler.ProfileData``
reads it.  A TPU shows up as planes named ``/device:TPU:<n>``: the line
``XLA Ops`` holds one event per device operation, the line ``XLA Modules``
one per execution of a compiled program.  The host plane holds the
benchmark's own spans (``jax.profiler.TraceAnnotation``), named
``bench.*``; ``bench.window`` brackets the measured window.

Everything is in nanoseconds on the profiler's one clock.  What is read:

* busy time: the union of the device's operation intervals inside the
  window; idle is the rest of the window;
* the step program's executions: modules whose name contains the name the
  driver gives (``record["step_program"]``), and the busy time inside them;
* collective time: operations whose name says ``collective-permute``;
* idle gaps: each stretch of the window with no operation on the device,
  named after the ``bench.*`` span that covers most of it on the host.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
WINDOW_SPAN = "bench.window"
COLLECTIVE = "collective-permute"
TOP = 10


@dataclasses.dataclass
class Device:
    ops: list          # [(name, start_ns, end_ns)]
    modules: list      # [(name, start_ns, end_ns)]


@dataclasses.dataclass
class Trace:
    devices: dict      # device id -> Device
    host: list         # [(span name, start_ns, end_ns)]


def load(path: str) -> Trace:
    """The device and host events of one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = Device(ops=[], modules=[])
            for line in plane.lines:
                into = {OPS_LINE: dev.ops, MODULES_LINE: dev.modules}.get(
                    line.name)
                if into is not None:
                    into.extend((e.name, e.start_ns, e.end_ns)
                                for e in line.events)
            devices[int(m.group(1))] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.end_ns)
                            for e in line.events
                            if e.name.startswith("bench."))
    return Trace(devices=devices, host=host)


def merge(intervals) -> np.ndarray:
    """Disjoint sorted (n, 2) array covering the union of ``intervals``."""
    iv = np.asarray(sorted((s, e) for s, e in intervals if e > s),
                    dtype=np.float64).reshape(-1, 2)
    if len(iv) == 0:
        return iv
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out)


def clip(union: np.ndarray, lo: float, hi: float) -> np.ndarray:
    if len(union) == 0:
        return union
    s = np.clip(union[:, 0], lo, hi)
    e = np.clip(union[:, 1], lo, hi)
    keep = e > s
    return np.stack([s[keep], e[keep]], axis=1)


def length(union: np.ndarray) -> float:
    return float((union[:, 1] - union[:, 0]).sum()) if len(union) else 0.0


def intersect(a: np.ndarray, b: np.ndarray) -> float:
    """Length of the intersection of two disjoint sorted unions."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i, 0], b[j, 0]), min(a[i, 1], b[j, 1])
        if hi > lo:
            total += hi - lo
        if a[i, 1] < b[j, 1]:
            i += 1
        else:
            j += 1
    return total


def complement(union: np.ndarray, lo: float, hi: float) -> np.ndarray:
    """The idle gaps of ``union`` inside [lo, hi]."""
    edges = [lo] + [x for se in union for x in se] + [hi]
    gaps = np.asarray(edges, dtype=np.float64).reshape(-1, 2)
    return gaps[gaps[:, 1] > gaps[:, 0]]


@dataclasses.dataclass
class Summary:
    """Per-device readings of one window, averaged over the devices."""

    window_ns: float
    n_devices: int
    busy_ns: float                 # mean over devices
    step_busy_ns: float | None     # mean over devices, inside step runs
    step_executions: float | None  # mean over devices
    step_gap_idle_ns: list         # idle between consecutive step runs
    collective_ns: float           # mean over devices
    top_ops: list                  # [(name, seconds)], mean over devices
    idle_gaps: list                # [(label, seconds)], longest first
    modules: dict                  # program name -> executions, all devices

    @property
    def window_s(self) -> float:
        return self.window_ns / 1e9

    @property
    def busy_s(self) -> float:
        return self.busy_ns / 1e9

    @property
    def idle_share(self) -> float | None:
        if not self.n_devices or not self.window_ns:
            return None
        return 1.0 - self.busy_ns / self.window_ns

    def breakdown(self) -> dict:
        return {"device_ops": [[n, s] for n, s in self.top_ops],
                "idle_gaps": [[n, s] for n, s in self.idle_gaps]}


def summarize(tr: Trace, step_program: str | None) -> Summary:
    windows = [(s, e) for n, s, e in tr.host if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"the trace has no {WINDOW_SPAN!r} span")
    lo, hi = windows[0]
    spans = [(n, s, e) for n, s, e in tr.host if n != WINDOW_SPAN]
    busy, step_busy, execs, gap_idle, coll = [], [], [], [], []
    op_time: dict[str, float] = {}
    modules: dict[str, int] = {}
    gaps = []
    for dev_id in sorted(tr.devices):
        dev = tr.devices[dev_id]
        ops = [(n, max(s, lo), min(e, hi)) for n, s, e in dev.ops
               if e > lo and s < hi]
        union = merge((s, e) for _, s, e in ops)
        busy.append(length(union))
        coll.append(sum(e - s for n, s, e in ops if COLLECTIVE in n))
        for n, s, e in ops:
            op_time[n] = op_time.get(n, 0.0) + (e - s)
        for n, s, e in dev.modules:
            if e > lo and s < hi:
                modules[n] = modules.get(n, 0) + 1
        if step_program is not None:
            runs = sorted((s, e) for n, s, e in dev.modules
                          if step_program in n and e > lo and s < hi)
            execs.append(len(runs))
            step_busy.append(intersect(union, clip(merge(runs), lo, hi)))
            for (_, e0), (s1, _) in zip(runs, runs[1:]):
                if s1 > e0:
                    gap_idle.append((s1 - e0) - length(clip(union, e0, s1)))
        for gs, ge in complement(union, lo, hi):
            gaps.append((_attribute(spans, gs, ge), (ge - gs) / 1e9))
    n = len(tr.devices)
    gaps.sort(key=lambda g: -g[1])
    top = sorted(op_time.items(), key=lambda kv: -kv[1])[:TOP]
    return Summary(
        window_ns=hi - lo, n_devices=n,
        busy_ns=float(np.mean(busy)) if busy else 0.0,
        step_busy_ns=float(np.mean(step_busy)) if step_busy else None,
        step_executions=float(np.mean(execs)) if execs else None,
        step_gap_idle_ns=gap_idle,
        collective_ns=float(np.mean(coll)) if coll else 0.0,
        top_ops=[(name, t / n / 1e9) for name, t in top],
        idle_gaps=gaps[:TOP], modules=modules)


def _attribute(spans, lo: float, hi: float) -> str:
    """The host span that covers most of [lo, hi], or ``"no bench span"``."""
    best, label = 0.0, "no bench span"
    for name, s, e in spans:
        cover = min(e, hi) - max(s, lo)
        if cover > best:
            best, label = cover, name
    return label


def reduce_dir(trace_dir: str, record: dict) -> Summary:
    """Summary of the one trace the profiler wrote under ``trace_dir``."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {trace_dir}, "
                                f"found {len(paths)}")
    return summarize(load(paths[0]), record.get("step_program"))
