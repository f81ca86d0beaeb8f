"""repro.obs — Cactus-style observability: timers, metrics, traces.

Cactus ships first-class performance reporting (per-thorn, per-schedule-
bin clocks printed as ``TimerReport``) and the CaKernel/Chemora lineage
closes the loop by feeding those measurements back into kernel tuning.
This package is that substrate for the reproduction, three pillars behind
one handle:

* :class:`~repro.obs.metrics.Registry` — labeled counters / gauges /
  histograms (``farm.slot_occupancy``, ``farm.queue_depth{priority}``,
  ``farm.compile_cache{result}``, ``sim.steps_total``,
  ``service.submit_to_result_seconds``), snapshottable to a dict.
* :class:`~repro.obs.timers.TimerTree` — hierarchical wall-clock timers
  around every schedule bin and every farm phase, rendered Cactus-style
  by :func:`report`.
* :class:`~repro.obs.trace.TraceLog` — per-simulation lifecycle events
  (submit -> admit -> first_step -> evict/readmit -> steady -> result),
  streamed as JSON-lines and exportable to Chrome trace-event format
  (Perfetto-loadable).

Spans are on whatever the telemetry: every ``section`` is a
``jax.profiler.TraceAnnotation`` (:func:`span`) on the profiler's clock,
its keyword counts recorded as the event's stats, and it never waits on
the device.  A profile of any run therefore shows the farm's host phases
and slot I/O by the names in :data:`SPANS`; with no profiler running a
span costs under a microsecond.

The contract that makes it safe to thread everywhere: **telemetry off is
bitwise-invisible**.  A disabled :class:`Telemetry` (the :data:`NULL`
singleton) makes every other hook a no-op — no timers, no
``jax.block_until_ready`` fences, no events — so the default execution
path computes byte-for-byte what the pre-telemetry one did.  Enable it
per-runtime (``repro.api.runtime(..., telemetry=True)``) or standalone::

    tel = repro.obs.telemetry(trace_path="events.jsonl")
    with tel.section("my_phase"):
        ...
    print(repro.obs.report(tel))
"""
from __future__ import annotations

import contextlib
import dataclasses
import json

from repro.obs.bench import (
    SCHEMA as BENCH_SCHEMA, host_info, load_bench, make_bench_doc,
    validate_bench, write_bench,
)
from repro.obs.health import (
    DIAG_COLUMNS, FlightRecorder, HealthConfig, HealthMonitor,
    load_flight_record, render_dashboard, resolve_health,
)
from repro.obs.metrics import Histogram, Registry, series_key
from repro.obs.timers import TimerNode, TimerTree
from repro.obs.trace import TraceLog, validate_chrome_trace

__all__ = [
    "BENCH_SCHEMA", "DIAG_COLUMNS", "FlightRecorder", "HealthConfig",
    "HealthMonitor", "Histogram", "NULL", "Registry", "Telemetry",
    "TelemetryConfig", "TimerNode", "TimerTree", "TraceLog", "host_info",
    "load_bench", "load_flight_record", "make_bench_doc",
    "SPANS", "render_dashboard", "report", "resolve", "resolve_health",
    "series_key", "span", "telemetry", "validate_bench",
    "validate_chrome_trace", "write_bench",
]

_NULL_CM = contextlib.nullcontext()

# Every span the program emits through :meth:`Telemetry.section` or
# :func:`span`.  The ``farm.*`` / ``ensemble.*`` / ``service.*`` /
# ``runtime.*`` names are emitted whatever the telemetry; ``schedule.<BIN>``
# only by a bin composed under enabled telemetry.
SPANS = (
    "service.run", "service.result_snapshot", "service.evict_spill",
    "service.readmit_restore",
    "farm.admit", "farm.step_chunk", "farm.harvest", "farm.health_drain",
    "farm.check_steady", "farm.quarantine", "farm.evict",
    "ensemble.write_slot", "ensemble.read_slot",
    "ensemble.write_slots", "ensemble.read_slots",
    "runtime.step",
    "schedule.INITIAL", "schedule.PRESTEP", "schedule.EVOL",
    "schedule.POSTSTEP", "schedule.ANALYSIS",
)


def span(name: str, **counts):
    """A host span on the profiler's clock: ``jax.profiler.TraceAnnotation``
    with ``counts`` as the event's stats.  It never syncs with the device,
    and costs under a microsecond when no profiler is running."""
    from jax.profiler import TraceAnnotation

    return TraceAnnotation(name, **counts)


@dataclasses.dataclass(frozen=True)
class TelemetryConfig:
    """How much to observe, and where the byproducts land.

    The heartbeat fields drive the service watchdog: a liveness file
    touched every ``heartbeat_interval_s`` (for an external
    orchestrator), and a stall recorded whenever consecutive beats are
    further apart than ``heartbeat_deadline_s``.
    """

    enabled: bool = True
    trace_path: str | None = None        # stream events as JSON-lines
    heartbeat_path: str | None = None    # liveness file (ft.watchdog)
    heartbeat_interval_s: float = 5.0
    heartbeat_deadline_s: float = 60.0


class Telemetry:
    """The live handle: one registry + one timer tree + one trace log."""

    enabled = True

    def __init__(self, config: TelemetryConfig | None = None, **kw):
        self.config = config if config is not None else TelemetryConfig(**kw)
        self.metrics = Registry()
        self.timers = TimerTree()
        self.trace = TraceLog(path=self.config.trace_path)
        global _CURRENT
        _CURRENT = self

    # -- hooks (every one a no-op on NULL) ------------------------------------
    @contextlib.contextmanager
    def section(self, name: str, **counts):
        """A :func:`span` that also feeds the nested wall-clock timer."""
        with span(name, **counts), self.timers.section(name):
            yield

    def fence(self, x):
        """``jax.block_until_ready`` so a section's clock covers the
        device work it dispatched.  Exists ONLY behind enabled telemetry:
        the off path adds no device syncs."""
        import jax

        try:
            return jax.block_until_ready(x)
        except Exception:   # non-array pytree leaves etc.
            return x

    # -- views ----------------------------------------------------------------
    def snapshot(self) -> dict:
        return {
            "metrics": self.metrics.snapshot(),
            "timers": self.timers.snapshot(),
            "n_events": len(self.trace.events),
        }

    def dump_json(self, path: str) -> str:
        with open(path, "w") as f:
            json.dump(self.snapshot(), f, indent=1, sort_keys=True)
        return path

    def report(self) -> str:
        """Human-readable timers + metrics summary (Cactus TimerReport)."""
        parts = ["== repro.obs report ==", self.timers.report()]
        m = self.metrics.report()
        if m:
            parts.append(m)
        if self.trace.events:
            parts.append(f"-- trace: {len(self.trace.events)} events --")
        return "\n".join(parts)

    def reset(self):
        self.metrics.reset()
        self.timers.reset()


class _NullTelemetry(Telemetry):
    """Disabled telemetry: every hook is a no-op; shared singleton."""

    enabled = False

    def __init__(self):
        self.config = TelemetryConfig(enabled=False)
        self.metrics = _NullRegistry()
        self.timers = _NullTimerTree()
        self.trace = _NullTraceLog()

    def section(self, name, **counts):
        return span(name, **counts)

    def fence(self, x):
        return x

    def report(self):
        return "== repro.obs report ==\n(telemetry disabled)"


class _NullRegistry(Registry):
    def inc(self, name, value=1, **labels):
        return 0

    def set(self, name, value, **labels):
        pass

    def observe(self, name, value, **labels):
        pass


class _NullTimerTree(TimerTree):
    def section(self, name):
        return _NULL_CM


class _NullTraceLog(TraceLog):
    def __init__(self):
        super().__init__(path=None)

    def emit(self, kind, sid=None, **data):
        return {}


NULL = _NullTelemetry()
_CURRENT: Telemetry = NULL


def telemetry(**kw) -> Telemetry:
    """Build an enabled :class:`Telemetry` (kwargs per TelemetryConfig)."""
    return Telemetry(TelemetryConfig(**kw))


def resolve(spec) -> Telemetry:
    """Coerce a user-facing telemetry spec to a live handle.

    Accepts: a Telemetry (passes through), None/False (disabled ->
    :data:`NULL`), True (fresh default-config handle), a
    :class:`TelemetryConfig`, or a dict of TelemetryConfig kwargs.
    """
    if isinstance(spec, Telemetry):
        return spec
    if spec is None or spec is False:
        return NULL
    if spec is True:
        return Telemetry()
    if isinstance(spec, TelemetryConfig):
        return Telemetry(spec) if spec.enabled else NULL
    if isinstance(spec, dict):
        cfg = TelemetryConfig(**spec)
        return Telemetry(cfg) if cfg.enabled else NULL
    raise TypeError(
        f"telemetry must be a Telemetry, TelemetryConfig, dict, or bool; "
        f"got {type(spec).__name__}")


def report(tel: Telemetry | None = None) -> str:
    """Render the handle's (default: the most recently enabled
    telemetry's) timer/metrics summary."""
    return (tel if tel is not None else _CURRENT).report()


def __getattr__(name: str):
    # repro.obs.perf pulls in the cost model / roofline chips (and, at
    # call time, jax + the farm stack) — lazy so `import repro.obs` stays
    # light and the farm's own top-level `from repro import obs` cannot
    # cycle through it
    if name == "perf":
        import importlib

        return importlib.import_module("repro.obs.perf")
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
