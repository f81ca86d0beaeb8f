"""repro.obs.perf — cost-model-grounded performance accounting.

PR 6's telemetry answers *where the wall-clock went*; this layer answers
*whether that time was any good* — the Cactus/CaKernel move of justifying
every kernel with hardware-grounded accounting.  It runs the
trip-count-aware HLO cost model (:mod:`repro.launch.hlo_cost`) over every
compiled executable the runtime produces — the serial schedule-bin step
and each per-static-signature farm executable, slots × shards
decomposition included — and joins the predicted cost (FLOPs, HBM bytes,
collective wire bytes) against the measured timer sections to report
achieved-vs-roofline utilization and a bottleneck classification
(compute / memory / collective) per row.

Halo traffic is double-entry bookkept: the decomposed ns3d step's
predicted ``collective-permute`` bytes (from the HLO) are compared
against the analytic ghost-zone byte count derived from
``plan_decomposition``'s active axes — :func:`halo_bytes_per_step`
mirrors the exchange sequence of ``NavierStokes3D._step_local`` exactly,
and the fast-lane test pins the two equal.

Executables that refuse both routes (optimized ``compile().as_text()``
and the pre-SPMD ``compiler_ir(dialect="hlo")`` fallback), or whose HLO
dialect the parser has not met, land as ``status="unparsed"`` rows — the
accounting never raises into a drive loop.

Surfaces: ``Runtime.report(perf=True)`` / ``Runtime.perf_report()``, the
``metrics["perf"]`` block of the ``repro.bench.v1`` envelope (consumed by
``benchmarks/check_regression.py``), and scrape-able gauges via
:meth:`PerfReport.export_gauges` behind
``SimulationService.prometheus_text()``.
"""
from __future__ import annotations

import dataclasses
import math

from repro.core.rooflinemodel import Chip, resolve_chip, terms_from_counts

PERF_SCHEMA = "repro.perf.v1"

# every attributed row carries at least these keys (the regression gate's
# contract with the bench envelope)
ROW_KEYS = ("name", "kind", "signature", "status", "n_devices", "flops",
            "hbm_bytes", "collective_wire_bytes", "invocations",
            "measured_s", "compute_s", "memory_s", "collective_s",
            "roofline_s", "bottleneck", "utilization")


@dataclasses.dataclass
class CostRow:
    """Predicted cost of ONE executable invocation, per device, plus the
    measured-time join.  ``flops``/``hbm_bytes``/``collective_wire_bytes``
    come from :func:`repro.launch.hlo_cost.safe_analyze`;
    ``measured_s``/``invocations`` from the PR 6 timer sections."""

    name: str
    kind: str                        # "farm-step" | "serial-bin"
    signature: str = "-"             # compile-cache static signature
    status: str = "ok"               # "ok" | "unparsed"
    n_devices: int = 1
    flops: float = 0.0
    hbm_bytes: float = 0.0
    collective_wire_bytes: float = 0.0
    collective_counts: dict = dataclasses.field(default_factory=dict)
    collective_bytes: dict = dataclasses.field(default_factory=dict)
    halo_bytes_predicted: float | None = None   # permute bytes from the HLO
    halo_bytes_analytic: float | None = None    # ghost-zone model
    invocations: int = 0
    measured_s: float | None = None  # wall seconds per invocation
    # health accounting (farm rows with a health monitor): ring-buffer
    # drains performed vs harvest boundaries crossed — equal means the
    # monitor added ZERO host syncs beyond the steady-check cadence
    health_drains: int | None = None
    health_boundaries: int | None = None
    error: str | None = None


# -- cost extraction ----------------------------------------------------------
def executable_hlo(jitted, *args) -> tuple[str, str]:
    """``(hlo_text, flavor)`` of ``jitted(*args)``.

    Prefers the optimized post-SPMD text (``lower().compile()``); when the
    host cannot run the program's mesh (AbstractMesh lowering, or more
    shards than devices) it falls back to the pre-SPMD
    ``compiler_ir(dialect="hlo")`` dump — still per-shard-shaped under
    ``shard_map``, with every ghost-face ``collective-permute`` explicit.
    """
    lowered = jitted.lower(*args)
    try:
        return lowered.compile().as_text(), "optimized"
    except Exception:
        return lowered.compiler_ir(dialect="hlo").as_hlo_text(), "pre-spmd"


def cost_row_from_hlo(hlo_text: str, *, name: str, kind: str,
                      signature: str = "-", n_devices: int = 1) -> CostRow:
    """Run the cost model over ``hlo_text``; parse failures record
    ``status="unparsed"`` instead of raising."""
    from repro.launch import hlo_cost

    cost, status, err = hlo_cost.safe_analyze(hlo_text, n_devices)
    row = CostRow(
        name=name, kind=kind, signature=signature, status=status,
        n_devices=n_devices, flops=float(cost.flops),
        hbm_bytes=float(cost.bytes),
        collective_wire_bytes=float(cost.collective_wire_bytes),
        collective_counts={k: float(v)
                           for k, v in cost.collective_counts.items()},
        collective_bytes={k: float(v)
                          for k, v in cost.collective_bytes.items()},
        error=err)
    if "collective-permute" in row.collective_bytes:
        row.halo_bytes_predicted = row.collective_bytes["collective-permute"]
    return row


# -- analytic halo model ------------------------------------------------------
def _norm_w(w) -> tuple[int, int]:
    if isinstance(w, int):
        return (w, w)
    lo, hi = w
    return (int(lo), int(hi))


def exchange_permute_bytes(local_shape, widths, active_axes,
                           itemsize: int = 4, *, padded: bool = True) -> int:
    """Per-device ``collective-permute`` operand bytes of ONE
    ``exchange_pad(u, widths, specs)`` call.

    Mirrors ``repro.core.halo._pad_axis`` exactly: axes pad sequentially
    (later axes exchange strips of the already-padded earlier axes — the
    corner trick), each decomposed axis side ships one strip of width
    ``w`` at the CURRENT padded shape, and non-decomposed axes still grow
    the shape by their BC padding.  ``padded=False`` counts
    ``halo.shifted_neighbours`` instead: every face at the unpadded shape.
    """
    shape = list(local_shape)
    total = 0
    for ax, w in enumerate(widths):
        lo, hi = _norm_w(w)
        if ax in active_axes:
            for side in (lo, hi):
                if side:
                    strip = list(shape)
                    strip[ax] = side
                    total += math.prod(strip) * itemsize
        if padded:
            shape[ax] += lo + hi
    return total


def padded_sweeps_per_step(config) -> int:
    """Jacobi sweeps of ONE ns3d step that build a padded copy of ``p``.

    None on the jnp template's single sweeps, which read ``p``'s face
    neighbours at its own shape (``halo.shifted_neighbours``); every loop
    trip under 3DBLOCK or the fused smoother (``fused_sweeps > 1``)."""
    k = max(config.fused_sweeps, 1)
    if k == 1 and (config.template or "JNP") == "JNP":
        return 0
    return max(config.jacobi_iters // k, 1)


def streamed_sweeps_per_step(config, local_shape, platform: str) -> int:
    """Jacobi sweeps of ONE ns3d step that stream ``p`` through VMEM
    (``kernels.jacobi.jacobi_pressure_streamed``): every loop trip of the
    jnp template's single sweeps on a TPU, where ``p`` of ``local_shape``
    outgrows what XLA stages on chip, its planes are whole (8, 128) tiles
    and y and z are periodic on no mesh axis (``jacobi.streams``); none
    elsewhere."""
    from repro.cfd.ns3d import periodic_axes
    from repro.core.halo import AxisSpec
    from repro.kernels.jacobi import streams

    if platform != "tpu" or config.fused_sweeps > 1 or \
            (config.template or "JNP") != "JNP":
        return 0
    split = dict(config.decomposition)
    specs = [AxisSpec(a, split.get(a), periodic)
             for a, periodic in enumerate(periodic_axes(config.case))]
    return max(config.jacobi_iters, 1) if streams(local_shape, specs) else 0


def _step_exchanges(config) -> list[tuple[tuple, int, bool]]:
    """``(widths, calls, padded)`` of the ghost fills of ONE ns3d step, in
    the order of ``NavierStokes3D._step_local``: three velocity fields at
    widths (1,1,1), and where the case carries a temperature
    (``ns3d.SCALAR_CASES``) four more: T, then the three velocities again
    for its advection; three one-sided divergence pads ((1,0),)*3; the
    Jacobi loop — ``max(jacobi_iters // max(fused_sweeps,1), 1)``
    iterations filling ``p``'s one-wide faces, padded or not
    (:func:`padded_sweeps_per_step`), or, when the communication-avoiding
    smoother is on, padding ``p`` and ``rhs`` at the sweep width; one
    one-sided projection pad ((0,1),)*3."""
    from repro.cfd.ns3d import SCALAR_CASES

    k = max(config.fused_sweeps, 1)
    iters = max(config.jacobi_iters // k, 1)
    sweeps = (((1, 1, 1), iters, padded_sweeps_per_step(config) > 0)
              if k <= 1 else ((k, k, k), 2 * iters, True))
    full = 3 + 4 * (config.case in SCALAR_CASES)
    return [((1, 1, 1), full, True), (((1, 0),) * 3, 3, True), sweeps,
            (((0, 1),) * 3, 1, True)]


def halo_bytes_per_step(config, active: dict, mesh_extents: dict, *,
                        slots_local: int = 1, itemsize: int = 4) -> int:
    """Analytic per-device ``collective-permute`` operand bytes of ONE
    decomposed ns3d step — the ground truth the HLO-predicted halo bytes
    are validated against.

    Mirrors the exchange sequence of ``NavierStokes3D._step_local``
    (:func:`_step_exchanges`).  ``active`` maps array axis -> mesh axis
    (``plan_decomposition``'s output); ``mesh_extents`` maps mesh axis ->
    extent; ``slots_local`` multiplies for the farm's per-device resident
    slots (the vmapped batch dimension rides inside every strip).  The
    in-situ health diagnostics add nothing here: their divergence stencil
    is interior-only (ghost-free by construction), so a health-monitored
    farm step moves exactly these bytes too.
    """
    local = list(config.shape)
    for ax, mesh_axis in active.items():
        local[ax] //= mesh_extents[mesh_axis]
    act = set(active)
    per_slot = sum(calls * exchange_permute_bytes(local, widths, act,
                                                  itemsize, padded=padded)
                   for widths, calls, padded in _step_exchanges(config))
    return per_slot * slots_local


def halo_permutes_per_step(config, active: dict) -> int:
    """``collective-permute`` ops ONE decomposed ns3d step runs per
    device: one per decomposed axis and nonzero ghost side of each
    ``exchange_pad`` call (a farm's slots ride inside the same ops)."""
    return sum(calls * sum(bool(side) for ax, w in enumerate(widths)
                           if ax in active for side in _norm_w(w))
               for widths, calls, _ in _step_exchanges(config))


def decomposed_step_hlo(config, *, n_slots: int, mesh_axes,
                        slot_axis: str = "slot") -> tuple[str, dict]:
    """``(hlo_text, active)`` of the slots × shards ensemble step lowered
    over an :class:`jax.sharding.AbstractMesh` — no devices needed.

    The fast-lane cost path: the pre-SPMD dump is per-shard-shaped inside
    ``shmap_body`` with one explicit ``collective-permute`` per ghost
    face, so the cost model sees exactly the decomposed traffic a real
    pod would ship.  ``mesh_axes`` is an ordered tuple of
    ``(name, extent)`` pairs, e.g. ``(("slot", 2), ("shard", 2))``.
    """
    import dataclasses as _dc

    import jax
    import jax.numpy as jnp
    from jax.sharding import AbstractMesh

    from repro.cfd.ns3d import PARAM_KEYS, NavierStokes3D
    from repro.sim.ensemble import make_ensemble_step, plan_decomposition

    mesh = AbstractMesh(tuple(e for _, e in mesh_axes),
                        tuple(n for n, _ in mesh_axes))
    solver_cfg, active = plan_decomposition(config, mesh,
                                            slot_axis=slot_axis)
    # the AbstractMesh satisfies the driver's axis-name/divisibility checks;
    # nothing device-touching (init_state/sharding) runs on this solver
    solver = NavierStokes3D(solver_cfg, mesh if active else None)
    step = make_ensemble_step(solver, mesh=mesh, slot_axis=slot_axis,
                              n_slots=n_slots)
    ref = NavierStokes3D(_dc.replace(solver_cfg, decomposition=()))
    one = jax.eval_shape(ref.init_state)
    state = {k: jax.ShapeDtypeStruct((n_slots,) + tuple(v.shape), v.dtype)
             for k, v in one.items()}
    params = {k: jax.ShapeDtypeStruct((n_slots,), jnp.float32)
              for k in PARAM_KEYS}
    lowered = step.lower(state, params, jax.ShapeDtypeStruct((), jnp.int32))
    return lowered.compiler_ir(dialect="hlo").as_hlo_text(), active


# -- runtime extraction -------------------------------------------------------
def _find_sections(timers: dict, name: str) -> tuple[float, int]:
    """Sum (total_s, count) over every node named ``name`` in a nested
    timer snapshot, wherever it nests."""
    tot, cnt = 0.0, 0

    def walk(children: dict):
        nonlocal tot, cnt
        for k, v in children.items():
            if k == name:
                tot += float(v.get("total_s", 0.0))
                cnt += int(v.get("count", 0))
            walk(v.get("children", {}))

    walk(timers or {})
    return tot, cnt


def _slots_local(n_slots: int, slot_extent: int) -> int:
    """Resident slots per device: the slot axis divides when it can,
    replicates otherwise (``sim.ensemble.slot_spec``'s guard)."""
    if slot_extent > 1 and n_slots % slot_extent == 0:
        return n_slots // slot_extent
    return n_slots


def farm_cost_row(service, *, signature: str = "-",
                  measured_s: float | None = None) -> CostRow:
    """Cost row of one ``SimulationService``'s compiled ensemble step
    (one invocation = one device step of the whole slot batch).  On a
    health-monitored farm the row also books the drain accounting
    (``health_drains`` performed vs ``health_boundaries`` crossed) so the
    report shows whether the monitor stayed on the harvest cadence."""
    ex = service.farm.exec
    farm = service.farm
    name = f"farm/{farm.farm_id}"
    n_dev = int(ex.mesh.size) if ex.mesh is not None else 1
    try:
        # step_args carries the health ring when enabled, so the lowered
        # executable is the one the farm actually runs
        text, _ = executable_hlo(ex._run_k, *ex.step_args(1))
    except Exception as e:
        return CostRow(name=name, kind="farm-step", signature=signature,
                       status="unparsed", n_devices=n_dev,
                       error=f"{type(e).__name__}: {e}")
    row = cost_row_from_hlo(text, name=name, kind="farm-step",
                            signature=signature, n_devices=n_dev)
    row.invocations = int(farm.device_steps)
    row.measured_s = measured_s
    if ex.decomposition and ex.mesh is not None:
        extents = dict(ex.mesh.shape)
        # the health diagnostics are ghost-free (interior stencil), so
        # the analytic halo count is the same with the monitor compiled in
        row.halo_bytes_analytic = float(halo_bytes_per_step(
            ex.solver.config, dict(ex.decomposition), extents,
            slots_local=_slots_local(ex.n_slots,
                                     extents.get(ex.slot_axis, 1))))
    if ex.health_window:
        row.health_drains = int(service.tel.metrics.get("health.drains")
                                or 0)
        row.health_boundaries = int(farm.device_steps
                                    // farm.check_steady_every)
    return row


def health_overhead_model(ex_off, ex_on, check_every: int) -> dict:
    """Deterministic steady-state price of the compiled-in health monitor.

    Lowers both executors' real ``run_k`` programs and runs the HLO cost
    model over them.  The chunk length ``k`` is a dynamic operand, so the
    model prices one loop iteration plus the chunk epilogue: exactly one
    device step for the health-off program, one step plus one
    diagnostics pass for the health-on program (the diagnostics sample
    the chunk's final state, outside the loop).  The steady overhead is
    therefore ``(bytes_on - bytes_off) / (check_every * bytes_off)`` —
    one diagnostics pass amortized over the ``check_steady_every`` steps
    whose chunk boundary its drain rides.  The stencil programs carry no
    dot/conv, so HBM traffic is the currency (the binding roofline axis
    for this solver).

    The bench gate holds this number to its bound instead of a
    wall-clock ratio: two separately compiled executables show
    several-percent process-level code-layout/scheduling variance on
    shared hosts (the sign of the difference flips between identical
    runs), which would turn a small wall gate into a coin flip, while
    the modeled byte count is bit-stable across runs and hosts.
    """
    rows = {}
    for tag, ex in (("off", ex_off), ("on", ex_on)):
        try:
            text, _ = executable_hlo(ex._run_k, *ex.step_args(check_every))
            rows[tag] = cost_row_from_hlo(text, name=f"health-model/{tag}",
                                          kind="health-model")
        except Exception as e:
            rows[tag] = CostRow(name=f"health-model/{tag}",
                                kind="health-model", status="unparsed",
                                error=f"{type(e).__name__}: {e}")
    off, on = rows["off"], rows["on"]
    ok = (off.status == "ok" and on.status == "ok" and off.hbm_bytes > 0)
    doc = {
        "status": "ok" if ok else "unparsed",
        "check_every": int(check_every),
        "hbm_bytes_step": off.hbm_bytes,
        "hbm_bytes_step_health": on.hbm_bytes,
        "hbm_bytes_diag_per_chunk": None,
        "modeled_overhead": None,
    }
    if ok:
        doc["hbm_bytes_diag_per_chunk"] = on.hbm_bytes - off.hbm_bytes
        doc["modeled_overhead"] = ((on.hbm_bytes - off.hbm_bytes)
                                   / (check_every * off.hbm_bytes))
    else:
        doc["error"] = off.error or on.error
    return doc


def serial_cost_row(prepared, *, label: str, timers: dict | None = None,
                    mesh=None) -> CostRow:
    """Cost row of one prepared serial run's EVOLVE bin (an uninstrumented
    twin of the bin is lowered, so telemetry wrappers never enter the
    HLO)."""
    import jax

    from repro.core.schedule import canonical_bin

    bname = canonical_bin("EVOLVE")
    name = f"serial/{label}/{bname}"
    active = dict(prepared.solver.domain.decomposition)
    n_dev = int(mesh.size) if (mesh is not None and active) else 1
    try:
        step = prepared.schedule.compile_bin(bname)
        text, _ = executable_hlo(jax.jit(step), prepared.state)
    except Exception as e:
        return CostRow(name=name, kind="serial-bin", status="unparsed",
                       n_devices=n_dev, error=f"{type(e).__name__}: {e}")
    row = cost_row_from_hlo(text, name=name, kind="serial-bin",
                            n_devices=n_dev)
    tot, cnt = _find_sections(timers or {}, f"schedule.{bname}")
    if cnt:
        row.invocations = cnt
        row.measured_s = tot / cnt
    if active and mesh is not None:
        row.halo_bytes_analytic = float(halo_bytes_per_step(
            prepared.solver.config, active, dict(mesh.shape)))
    return row


def report_for_runtime(rt, chip: Chip | str = "auto",
                       dtype: str = "f32") -> "PerfReport":
    """The runtime's full perf accounting: one row per farm signature
    (``farm.step_chunk`` seconds / device steps as the measured join) and
    one per prepared serial scenario (``schedule.EVOLVE`` sections).

    When several farms share one telemetry handle their step-chunk time
    cannot be told apart, so the per-device-step seconds are the
    aggregate across farms — honest for the single-signature common case
    and clearly labeled either way.
    """
    timers = rt.telemetry.timers.snapshot() if rt.telemetry.enabled else {}
    rows: list[CostRow] = []
    services = getattr(rt, "_services", {})
    total_steps = sum(svc.farm.device_steps for svc in services.values())
    chunk_tot, _ = _find_sections(timers, "farm.step_chunk")
    per_step = (chunk_tot / total_steps
                if total_steps and chunk_tot else None)
    for key, svc in services.items():
        rows.append(farm_cost_row(svc, signature=str(key),
                                  measured_s=per_step))
    for label, pr in getattr(rt, "_prepared", {}).items():
        rows.append(serial_cost_row(pr, label=label, timers=timers,
                                    mesh=rt.mesh))
    return PerfReport(rows, chip=resolve_chip(chip), dtype=dtype)


# -- the report ---------------------------------------------------------------
class PerfReport:
    """Attributed cost rows against one chip's roofline."""

    def __init__(self, rows, *, chip: Chip | str = "auto",
                 dtype: str = "f32"):
        self.costs: list[CostRow] = list(rows)
        self.chip = resolve_chip(chip)
        self.dtype = dtype

    def _attribute(self, c: CostRow) -> dict:
        d = dataclasses.asdict(c)
        terms = terms_from_counts(c.flops, c.hbm_bytes,
                                  c.collective_wire_bytes,
                                  dtype=self.dtype, chip=self.chip)
        d.update(
            compute_s=terms.compute_s, memory_s=terms.memory_s,
            collective_s=terms.collective_s, roofline_s=terms.step_time_s,
            bottleneck=terms.bottleneck if c.status == "ok" else "unknown")
        if c.status == "ok" and c.measured_s and c.measured_s > 0:
            d["achieved_flops_s"] = c.flops / c.measured_s
            # fraction of the roofline-optimistic time actually achieved;
            # left uncapped so a model underestimate stays visible
            d["utilization"] = (terms.step_time_s / c.measured_s
                                if terms.step_time_s else None)
        else:
            d["achieved_flops_s"] = None
            d["utilization"] = None
        ha, hp = c.halo_bytes_analytic, c.halo_bytes_predicted
        d["halo_match"] = (
            None if ha is None or hp is None
            else bool(abs(ha - hp) <= 1e-6 * max(abs(ha), abs(hp), 1.0)))
        return d

    def rows(self) -> list[dict]:
        return [self._attribute(c) for c in self.costs]

    def as_dict(self) -> dict:
        return {
            "schema": PERF_SCHEMA,
            "chip": {"name": self.chip.name,
                     "peak_flops": self.chip.peak_flops(self.dtype),
                     "hbm_bandwidth": self.chip.hbm_bandwidth,
                     "ici_link_bandwidth": self.chip.ici_link_bandwidth},
            "dtype": self.dtype,
            "rows": self.rows(),
        }

    def render(self) -> str:
        lines = [f"-- perf accounting (chip {self.chip.name}, "
                 f"{self.dtype} peak {self.chip.peak_flops(self.dtype):.3g} "
                 f"FLOP/s, HBM {self.chip.hbm_bandwidth:.3g} B/s) --"]
        if not self.costs:
            lines.append("  (no executables accounted — enable telemetry "
                         "and run something first)")
            return "\n".join(lines)
        hdr = (f"  {'row':<34} {'status':<8} {'flops/inv':>10} "
               f"{'HBM B/inv':>10} {'wire B/inv':>10} {'bottleneck':<10} "
               f"{'measured_s':>10} {'util':>6}")
        lines.append(hdr)
        for d in self.rows():
            ms = f"{d['measured_s']:.3g}" if d["measured_s"] else "-"
            ut = f"{d['utilization']:.3g}" if d["utilization"] else "-"
            lines.append(
                f"  {d['name']:<34} {d['status']:<8} {d['flops']:>10.3g} "
                f"{d['hbm_bytes']:>10.3g} "
                f"{d['collective_wire_bytes']:>10.3g} "
                f"{d['bottleneck']:<10} {ms:>10} {ut:>6}")
            if d["collective_counts"]:
                coll = "  ".join(
                    f"{k}×{int(v)} ({d['collective_bytes'].get(k, 0):.3g} B)"
                    for k, v in sorted(d["collective_counts"].items()))
                lines.append(f"      collectives: {coll}")
            if d["halo_bytes_analytic"] is not None:
                verdict = {True: "MATCH", False: "MISMATCH",
                           None: "?"}[d["halo_match"]]
                lines.append(
                    f"      halo bytes: predicted "
                    f"{d['halo_bytes_predicted'] or 0:.6g} vs analytic "
                    f"{d['halo_bytes_analytic']:.6g} — {verdict}")
            if d.get("health_drains") is not None:
                lines.append(
                    f"      health: {d['health_drains']} ring drains over "
                    f"{d['health_boundaries']} harvest boundaries "
                    f"(extra host syncs: "
                    f"{d['health_drains'] - d['health_boundaries']})")
            if d["error"]:
                lines.append(f"      error: {d['error']}")
        return "\n".join(lines)

    def export_gauges(self, registry, prefix: str = "perf"):
        """Mirror the attributed rows into scrape-able gauges (the
        Prometheus surface behind ``SimulationService.prometheus_text``)."""
        for d in self.rows():
            row = d["name"]
            registry.set(f"{prefix}.flops_per_invocation", d["flops"],
                         row=row)
            registry.set(f"{prefix}.hbm_bytes_per_invocation",
                         d["hbm_bytes"], row=row)
            registry.set(f"{prefix}.collective_wire_bytes_per_invocation",
                         d["collective_wire_bytes"], row=row)
            registry.set(f"{prefix}.roofline_s", d["roofline_s"], row=row)
            registry.set(f"{prefix}.bottleneck", 1.0, row=row,
                         kind=d["bottleneck"])
            if d["utilization"] is not None:
                registry.set(f"{prefix}.utilization", d["utilization"],
                             row=row)
            if d["achieved_flops_s"] is not None:
                registry.set(f"{prefix}.achieved_flops_s",
                             d["achieved_flops_s"], row=row)
        return registry


def validate_perf(doc: dict) -> dict:
    """Schema check for an embedded ``repro.perf.v1`` block; returns the
    doc or raises ``ValueError`` naming every problem at once."""
    problems = []
    if not isinstance(doc, dict):
        raise ValueError(f"perf block must be a dict, got {type(doc)}")
    if doc.get("schema") != PERF_SCHEMA:
        problems.append(f"schema is {doc.get('schema')!r}, "
                        f"expected {PERF_SCHEMA!r}")
    if not isinstance(doc.get("chip"), dict) or "name" not in doc.get(
            "chip", {}):
        problems.append("chip must be a dict with a 'name'")
    rows = doc.get("rows")
    if not isinstance(rows, list):
        problems.append("rows must be a list")
    else:
        for i, r in enumerate(rows):
            missing = [k for k in ROW_KEYS if k not in r]
            if missing:
                problems.append(f"row {i} missing {missing}")
    if problems:
        raise ValueError("invalid perf block: " + "; ".join(problems))
    return doc
