"""The simulation farm: continuous batching of CFD runs over fixed slots.

Scheduling policy for the :class:`~repro.sim.ensemble.EnsembleExecutor`:
requests queue up host-side; whenever a slot frees (target step count hit or
steady state detected), the next request is admitted into it and the whole
batch keeps stepping — the vLLM pattern with CFD steps in place of token
decodes.  Admission writes the case's initial fields (or an evicted
simulation's saved fields) into the slot and installs its per-simulation
scalars; nothing ever recompiles, because the compiled ensemble step depends
only on the *static* configuration (case, grid shape, tile/template, solver
structure, slot count).

Those compiled steps live in a process-wide cache keyed by that static
signature, so a second farm — or a farm restarted after drain — of an
already-seen shape reuses the executable (hit/miss counters exposed via
:func:`compile_cache_stats` and asserted by the test suite).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any

import jax

from repro import obs
from repro.cfd.ns3d import CFDConfig, NavierStokes3D, host_params
from repro.sim.ensemble import (
    EnsembleExecutor, make_ensemble_step, plan_decomposition,
)
from repro.sim.slots import SlotTable


# -- compile cache -----------------------------------------------------------
# The executable cache stays process-wide on purpose (a restarted farm of a
# seen shape reuses the compiled step); the hit/miss COUNTERS are metrics:
# each farm scopes them to its own telemetry registry, so back-to-back
# runtimes no longer report each other's hits.  ``_FACADE_METRICS`` backs
# the legacy module-level ``compile_cache_stats()`` facade, which keeps its
# process-global semantics for compatibility.
_STEP_CACHE: dict[tuple, tuple[NavierStokes3D, Any]] = {}
_FACADE_METRICS = obs.Registry()

CACHE_METRIC = "farm.compile_cache"


def _count_cache(result: str, metrics=None):
    _FACADE_METRICS.inc(CACHE_METRIC, result=result)
    if metrics is not None and metrics is not _FACADE_METRICS:
        metrics.inc(CACHE_METRIC, result=result)


def static_key(config: CFDConfig, n_slots: int) -> tuple:
    """The compile signature: everything that selects the executable.

    Per-simulation physics (nu, dt, lid velocity, forcing) is deliberately
    absent — it is threaded through the step as traced scalars, so admitting
    a new parameter variant of a seen shape never recompiles.
    """
    return (
        config.case, config.shape, config.extent, config.jacobi_iters,
        config.jacobi_omega, config.fused_sweeps, config.template,
        config.interpret, config.overlap, config.decomposition, n_slots,
    )


def compiled_ensemble_step(config: CFDConfig, n_slots: int, mesh=None,
                           slot_axis: str = "data", metrics=None,
                           health_window: int = 0):
    """(solver, jitted chunked ensemble step) for the static signature.

    ``mesh`` extends the signature (a Mesh is hashable): multi-device
    farms cache separately from single-device ones of the same shape.
    With ``config.decomposition`` set, the solver is built against the
    farm mesh so each slot's grid decomposes over the named axes (the
    slots × shards path); a mesh whose decomposed axes all have extent 1
    degrades to the plain slot-parallel executable.

    ``health_window`` also extends the cache key — the in-situ health
    ring changes the executable's signature — but NOT ``static_key``
    itself: request admission matches on the physics signature alone, so
    the same requests run on health-on and health-off farms unchanged.

    ``metrics`` (an :class:`repro.obs.Registry`) additionally receives
    the ``farm.compile_cache{result=hit|miss}`` counters, scoping cache
    stats to the caller's telemetry instead of only the process facade.
    """
    key = static_key(config, n_slots) + (mesh, slot_axis if mesh else None,
                                         health_window)
    hit = _STEP_CACHE.get(key)
    if hit is not None:
        _count_cache("hit", metrics)
        return hit
    _count_cache("miss", metrics)
    solver_cfg, decomp = plan_decomposition(
        config, mesh, slot_axis=slot_axis if mesh is not None else None)
    solver = NavierStokes3D(solver_cfg, mesh if decomp else None)
    _STEP_CACHE[key] = (solver, make_ensemble_step(
        solver, mesh=mesh, slot_axis=slot_axis, n_slots=n_slots,
        health_window=health_window))
    return _STEP_CACHE[key]


def compile_cache_stats(metrics=None) -> dict:
    """Hit/miss/entry counts — process-wide by default (the legacy
    facade), or scoped to a telemetry registry when one is passed."""
    reg = metrics if metrics is not None else _FACADE_METRICS
    return {"hits": reg.get(CACHE_METRIC, result="hit") or 0,
            "misses": reg.get(CACHE_METRIC, result="miss") or 0,
            "entries": len(_STEP_CACHE)}


def reset_compile_cache():
    _STEP_CACHE.clear()
    _FACADE_METRICS.reset()


# -- requests / results ------------------------------------------------------
@dataclasses.dataclass
class SimRequest:
    """One simulation: a full per-run config + how long to run it.

    The config's static part must match the farm's; its scalar part (nu, dt,
    lid velocity, forcing) is what makes this run *this* run.  ``steps`` is
    the target device-step count.  Two early-termination criteria compose
    (first hit wins): ``residual_tol`` stops once the steady-state residual
    ``||u^{n+1} - u^n||_inf / dt`` falls below it (the physical criterion);
    ``steady_tol`` is the legacy relative kinetic-energy-drift heuristic.
    Both are evaluated on the farm's global ``check_steady_every`` cadence
    (not per-sim step counts), so a sim admitted off a check boundary may
    terminate at a different step than a serial run of the same request —
    admissions into an idle farm are boundary-aligned and match exactly.
    ``priority`` orders admission: higher levels leave the queue first,
    FIFO within a level.  ``init_state``/``step0`` readmit an evicted
    simulation mid-flight.
    """

    config: CFDConfig
    steps: int
    tag: str = ""
    steady_tol: float | None = None
    residual_tol: float | None = None
    priority: int = 0
    init_state: dict | None = None
    step0: int = 0
    sid: int | None = None   # assigned by the farm


@dataclasses.dataclass
class SimResult:
    sid: int
    tag: str
    steps_done: int
    terminated: str    # "steps" | "steady" | "residual" | "failed" | "diverged"
    state: dict              # host arrays: vx, vy, vz, p (+ masks)
    config: CFDConfig
    error: str | None = None   # set iff terminated is "failed" / "diverged"


class _SlotEntry:
    """Host bookkeeping for one resident simulation."""

    __slots__ = ("req", "steps_done", "ke_prev", "started")

    def __init__(self, req: SimRequest):
        self.req = req
        self.steps_done = req.step0
        self.ke_prev: float | None = None
        self.started = False           # first step-chunk already traced?


class SimulationFarm:
    """Queue + slots + termination around one compiled ensemble step.

    The host phases are profiler spans (``farm.admit``,
    ``farm.step_chunk``, ``farm.harvest``, ... in ``repro.obs.SPANS``)
    whatever the telemetry.  ``telemetry`` (any :func:`repro.obs.resolve`
    spec) adds hierarchical timers over those phases, ``farm.*`` /
    ``sim.*`` metrics, and per-sim lifecycle trace events.  Disabled (the
    default) those hooks are no-ops — results are bitwise those of an
    uninstrumented farm, with no extra device syncs.
    ``farm_id`` tags this farm's trace events when several farms share
    one telemetry handle (the Runtime's one-service-per-signature case).

    Slot I/O moves by host round, not by member: a round's admissions are
    one ``EnsembleExecutor.write_slots`` call and the sims that finish at
    one boundary one ``read_slots`` call, so a wave of many members costs
    the device about what one member does.

    ``health`` (any :func:`repro.obs.health.resolve_health` spec) turns
    on in-situ health monitoring: the compiled step accumulates per-sim
    physics diagnostics into a device ring buffer, drained at the same
    ``check_steady_every`` boundary the steady checks use (zero extra
    steady-state host syncs), and a NaN/diverged sim is quarantined —
    evicted with ``terminated="diverged"`` and flight-recorded — while
    the remaining slots keep stepping bitwise-identically to a farm that
    never admitted it.  Health is independent of ``telemetry``:
    quarantine is functional behavior; events/metrics simply no-op when
    telemetry is off.
    """

    def __init__(self, base_config: CFDConfig, n_slots: int = 8,
                 check_steady_every: int = 16, mesh=None,
                 slot_axis: str = "data", telemetry=None,
                 farm_id: str | None = None, health=None):
        from repro.obs.health import (
            FlightRecorder, HealthMonitor, resolve_health,
        )

        self.base_config = base_config
        self.n_slots = n_slots
        self.check_steady_every = check_steady_every
        self.tel = obs.resolve(telemetry)
        self.farm_id = farm_id if farm_id is not None else base_config.case
        self.health = resolve_health(health)
        hw = self.health.window if self.health is not None else 0
        solver, run_k = compiled_ensemble_step(base_config, n_slots,
                                               mesh=mesh,
                                               slot_axis=slot_axis,
                                               metrics=self.tel.metrics,
                                               health_window=hw)
        self.exec = EnsembleExecutor(base_config, n_slots,
                                     solver=solver, run_k=run_k, mesh=mesh,
                                     slot_axis=slot_axis,
                                     telemetry=self.tel,
                                     health_window=hw)
        self.monitor = (HealthMonitor(self.health, telemetry=self.tel,
                                      farm_id=self.farm_id)
                        if self.health is not None else None)
        self.flight = (FlightRecorder(self.health.flight_dir)
                       if self.health is not None
                       and self.health.flight_dir else None)
        self.table = SlotTable(n_slots)
        self.results: dict[int, SimResult] = {}
        self.device_steps = 0
        self._next_sid = 0
        self._live: set[int] = set()   # queued or resident sids
        self._submit_ts: dict[int, float] = {}   # sid -> submit wall time
        self.heartbeat = None          # service-installed: fn(chunk_wall_s)
        # service-installed durable-store hook: fn(kind, req, result, **info)
        # fired at admission ("running") and at every terminal resolution
        # ("done"/"failed"/"diverged"), so each lifecycle transition lands
        # in the job store right where the state change happens.  None (the
        # default) keeps the in-memory path bitwise-untouched.
        self.on_transition = None

    def _gauge_load(self):
        """Refresh the occupancy/queue-depth gauges (telemetry only)."""
        if not self.tel.enabled:
            return
        self.tel.metrics.set("farm.slot_occupancy", self.table.n_active)
        for prio, depth in self.table.queue_depths().items():
            self.tel.metrics.set("farm.queue_depth", depth, priority=prio)

    # -- intake ---------------------------------------------------------------
    def submit(self, req: SimRequest) -> int:
        """Queue a simulation; returns its sid (poll/result handle)."""
        if static_key(req.config, self.n_slots) != static_key(
                self.base_config, self.n_slots):
            raise ValueError(
                "request's static config does not match this farm: "
                f"{static_key(req.config, self.n_slots)} vs "
                f"{static_key(self.base_config, self.n_slots)}")
        if req.steps < 0:
            raise ValueError(f"steps must be >= 0, got {req.steps}")
        if req.sid is None:
            req.sid = self._next_sid
            self._next_sid += 1
        elif req.sid in self._live or req.sid in self.results:
            # a request object is a one-shot ticket: resubmitting it while
            # its sid is queued/resident/finished would silently alias two
            # simulations onto one handle
            raise ValueError(f"sid {req.sid} is already submitted")
        else:
            # caller-set sid (readmission): reserve it so auto-assignment
            # can never alias a fresh request onto the same handle
            self._next_sid = max(self._next_sid, req.sid + 1)
        self._live.add(req.sid)
        self.table.submit(req, priority=req.priority)
        if self.tel.enabled:
            self._submit_ts.setdefault(req.sid, time.perf_counter())
            kind = "submit" if req.step0 == 0 else "readmit_submit"
            self.tel.trace.emit(
                kind, sid=req.sid, farm=self.farm_id, tag=req.tag,
                priority=req.priority, steps=req.steps, step0=req.step0,
                signature=str(static_key(req.config, self.n_slots)))
            self._gauge_load()
        return req.sid

    def _admit(self):
        """Admit queued work into every free slot: each round's members
        are written by one ``write_slots`` call, and a steps=0 member is
        harvested at once, which frees its slot for the next round."""
        with self.tel.section("farm.admit"):
            while True:
                admits = self._collect_admits()
                if not admits:
                    break
                try:
                    self.exec.write_slots([(slot, params, state) for
                                           slot, _, params, state in admits])
                except Exception as e:
                    # the round's one dispatch failed: no member of it
                    # holds its slot
                    for slot, entry, _, _ in admits:
                        self._fail(slot, entry, e)
                    continue
                done = []
                for slot, entry, _, _ in admits:
                    if self.on_transition is not None:
                        self.on_transition("running", entry.req, None)
                    if entry.steps_done >= entry.req.steps:
                        # already at (or past) its target: harvest without
                        # stepping, so a steps=0 request never advances
                        # the batch
                        done.append((slot, entry, "steps"))
                if not done:
                    break
                self._harvest(done)
            self._gauge_load()

    def _collect_admits(self) -> list:
        """Take queued requests into free slots: ``(slot, entry, host
        params, host state or None)`` for each.  A request whose scalars or
        readmission state do not fit a slot fails alone, recorded as a
        per-sim failed result, and its slot takes the next request."""
        admits = []
        while True:
            admitted = self.table.admit_next()
            if admitted is None:
                return admits
            slot, req = admitted
            # replace the queued request with live bookkeeping
            entry = _SlotEntry(req)
            self.table.replace(slot, entry)
            self.tel.trace.emit("admit", sid=req.sid, farm=self.farm_id,
                                slot=slot, step0=req.step0, tag=req.tag)
            if self.monitor is not None:
                # rows stamped <= the current device step belong to the
                # slot's previous occupant
                self.monitor.admit(req.sid, slot, tag=req.tag,
                                   last_step=self.device_steps - 1)
            try:
                params = host_params(req.config)
                state = (None if req.init_state is None
                         else self.exec.check_state(req.init_state))
            except Exception as e:
                # a bad request (mis-shaped readmission fields, ...) must
                # fail alone instead of poisoning the round or leaving its
                # sid queued or running forever
                self._fail(slot, entry, e)
                continue
            admits.append((slot, entry, params, state))

    # -- stepping -------------------------------------------------------------
    def _chunk_size(self, max_chunk: int | None) -> int:
        """Device steps until the next host decision point.

        The batch can run on-device (one dispatch, ``fori_loop``) until the
        earliest of: a slot hitting its target step count (slot reclamation
        + admission happen then), the next steady-state check boundary, or
        the caller's budget.  Chunking is numerics-neutral — tested bitwise
        against single-stepping.
        """
        chunk = min(e.req.steps - e.steps_done
                    for _, e in self.table.occupied())
        if self.monitor is not None or any(
                e.req.steady_tol is not None or e.req.residual_tol is not None
                for _, e in self.table.occupied()):
            # health drains share the steady-check cadence: cap the chunk
            # at the boundary so the ring is read exactly there
            boundary = self.check_steady_every - (
                self.device_steps % self.check_steady_every)
            chunk = min(chunk, boundary)
        if max_chunk is not None:
            chunk = min(chunk, max_chunk)
        return max(chunk, 1)

    def step(self, max_chunk: int | None = None) -> int:
        """Admit waiting work, advance the batch one chunk, harvest
        finishers.  Returns the number of device steps taken (0 when the
        farm is empty, or when the chunk failed — the failure is recorded
        as per-sim "failed" results, never re-raised into the drive loop)."""
        self._admit()
        if self.table.n_active == 0:
            return 0
        chunk = self._chunk_size(max_chunk)
        watch_resid = any(e.req.residual_tol is not None
                          for _, e in self.table.occupied())
        at_boundary = (self.device_steps + chunk) % self.check_steady_every == 0
        resid = None
        want_wall = self.tel.enabled or self.heartbeat is not None
        t_chunk = time.perf_counter() if want_wall else 0.0
        try:
            with self.tel.section("farm.step_chunk"):
                if watch_resid and at_boundary:
                    # land the final device step alone: the residual
                    # ||u^{n+1} - u^n||_inf compares consecutive states, and
                    # chunk splitting is numerics-neutral (frozen contract)
                    if chunk > 1:
                        self.exec.step_many(chunk - 1)
                    prev = self.exec.state
                    self.exec.step_many(1)
                    resid = self.exec.residuals(prev)
                else:
                    self.exec.step_many(chunk)
                # the fence exists only behind enabled telemetry: it makes
                # the section's clock (and the watchdog's view) cover the
                # dispatched device work, never the default path
                self.tel.fence(self.exec.state)
        except Exception as e:
            # the compiled step itself failed (first-trace/compile error):
            # it is shared by every resident sim, so all of them fail
            for slot, entry in list(self.table.occupied()):
                self._fail(slot, entry, e)
            return 0
        if self.tel.enabled:
            self.tel.metrics.inc("sim.steps_total",
                                 chunk * self.table.n_active)
            for _, entry in self.table.occupied():
                if not entry.started:
                    entry.started = True
                    self.tel.trace.emit("first_step", sid=entry.req.sid,
                                        farm=self.farm_id,
                                        device_step=self.device_steps)
        if self.heartbeat is not None:
            # service watchdog hook: chunk wall time + liveness beat
            self.heartbeat(time.perf_counter() - t_chunk)
        self.device_steps += chunk
        for slot, entry in list(self.table.occupied()):
            entry.steps_done += chunk
        # drain + quarantine BEFORE the steps-target harvest: a sim that
        # goes bad in the chunk that would also have finished it reports
        # "diverged", not a healthy-looking "steps" result
        self._drain_health()
        self._harvest([(slot, entry, "steps")
                       for slot, entry in self.table.occupied()
                       if entry.steps_done >= entry.req.steps])
        self._check_steady(resid)
        return chunk

    def _drain_health(self):
        """Read the device health ring (ONE host sync) at a harvest
        boundary, run every resident sim's state machine, quarantine the
        NaN/diverged ones."""
        if (self.monitor is None
                or self.device_steps % self.check_steady_every):
            return
        occupied = list(self.table.occupied())
        if not occupied:
            return
        with self.tel.section("farm.health_drain"):
            rings = self.exec.read_health()
        self.tel.metrics.inc("health.drains")
        from repro.obs.health import DIVERGED, NAN

        for slot, entry in occupied:
            rec = self.monitor.observe(entry.req.sid, rings[slot])
            if rec.state in (DIVERGED, NAN) and self.health.quarantine:
                self._quarantine(slot, entry, rec)
        self.monitor.export_gauges()

    def _quarantine(self, slot: int, entry: _SlotEntry, rec):
        """Evict a NaN/diverged sim: flight-record its last-K health
        frames + final (poisoned) state, resolve it with
        ``terminated="diverged"``, free the slot.  The surviving slots
        never see any of this — slots are independent under vmap, so
        they keep stepping bitwise as if the bad sim was never admitted.
        """
        req = entry.req
        with self.tel.section("farm.quarantine"):
            state = self.exec.read_slot(slot)
        flight_path = None
        if self.flight is not None:
            flight_path = self.flight.record(
                req.sid, frames=rec.frames_array(), state=state,
                meta={"tag": req.tag, "farm": self.farm_id, "slot": slot,
                      "state": rec.state, "cause": rec.cause,
                      "steps_done": entry.steps_done,
                      "device_step": self.device_steps,
                      "thresholds": dataclasses.asdict(self.health),
                      "signature": str(static_key(req.config,
                                                  self.n_slots))})
        err = (f"health: {rec.state} ({rec.cause}) at device step "
               f"{self.device_steps}"
               + (f"; flight record: {flight_path}" if flight_path else ""))
        self.results[req.sid] = SimResult(
            sid=req.sid, tag=req.tag, steps_done=entry.steps_done,
            terminated="diverged", state=state, config=req.config,
            error=err)
        self._live.discard(req.sid)
        self.table.release(slot)
        self.exec.clear_slot(slot)
        self.monitor.release(req.sid)
        self.tel.metrics.inc("health.quarantines")
        self._resolved(req, entry.steps_done, "diverged", error=err)
        if self.on_transition is not None:
            self.on_transition("diverged", req, self.results[req.sid],
                               flight_path=flight_path)

    def _check_steady(self, resid=None):
        if self.device_steps % self.check_steady_every:
            return
        with self.tel.section("farm.check_steady"):
            done = []
            if resid is not None:
                for slot, entry in self.table.occupied():
                    tol = entry.req.residual_tol
                    if tol is not None and float(resid[slot]) <= tol:
                        done.append((slot, entry, "residual"))
            stopped = {slot for slot, _, _ in done}
            watched = [(s, e) for s, e in self.table.occupied()
                       if e.req.steady_tol is not None and s not in stopped]
            if watched:
                ke = self.exec.kinetic_energy()
                for slot, entry in watched:
                    k = float(ke[slot])
                    prev = entry.ke_prev
                    entry.ke_prev = k
                    if prev is not None and abs(k - prev) <= \
                            entry.req.steady_tol * max(abs(k), 1e-12):
                        done.append((slot, entry, "steady"))
            self._harvest(done)

    def _harvest(self, done: list):
        """Resolve a round's finished sims, ``(slot, entry, reason)``
        each: their fields come off the device in one ``read_slots``."""
        if not done:
            return
        with self.tel.section("farm.harvest"):
            states = self.exec.read_slots([slot for slot, _, _ in done])
            for (slot, entry, reason), state in zip(done, states):
                req = entry.req
                self.results[req.sid] = SimResult(
                    sid=req.sid, tag=req.tag, steps_done=entry.steps_done,
                    terminated=reason, state=state, config=req.config)
                self._live.discard(req.sid)
                self.table.release(slot)
                self.exec.clear_slot(slot)
                if self.monitor is not None:
                    self.monitor.release(req.sid)
                self._resolved(req, entry.steps_done, reason)
                if self.on_transition is not None:
                    self.on_transition("done", req, self.results[req.sid])

    def _fail(self, slot: int, entry: _SlotEntry, exc: BaseException):
        """Record a per-sim failure as a harvestable result and free the
        slot — a sim whose admission or step raised must surface through
        poll/result/drain instead of wedging the farm."""
        req = entry.req
        err = f"{type(exc).__name__}: {exc}"
        self.results[req.sid] = SimResult(
            sid=req.sid, tag=req.tag, steps_done=entry.steps_done,
            terminated="failed", state={}, config=req.config, error=err)
        self._live.discard(req.sid)
        self.table.release(slot)
        self.exec.clear_slot(slot)
        if self.monitor is not None:
            self.monitor.release(req.sid)
        self._resolved(req, entry.steps_done, "failed", error=err)
        if self.on_transition is not None:
            self.on_transition("failed", req, self.results[req.sid])

    def _resolved(self, req: SimRequest, steps_done: int, reason: str,
                  error: str | None = None):
        """Telemetry for a sid leaving the farm (finished or failed)."""
        if not self.tel.enabled:
            return
        if reason in ("steady", "residual"):
            self.tel.trace.emit("steady", sid=req.sid, farm=self.farm_id,
                                criterion=reason, steps_done=steps_done)
        extra = {"error": error} if error else {}
        self.tel.trace.emit("result", sid=req.sid, farm=self.farm_id,
                            terminated=reason, steps_done=steps_done,
                            tag=req.tag, **extra)
        self.tel.metrics.inc("sim.results", terminated=reason)
        t0 = self._submit_ts.pop(req.sid, None)
        if t0 is not None:
            self.tel.metrics.observe("service.submit_to_result_seconds",
                                     time.perf_counter() - t0,
                                     priority=req.priority)
        self._gauge_load()

    def run(self, max_device_steps: int, until=None) -> int:
        """Step until the budget, the farm drains, or ``until()`` is true.

        ``max_device_steps`` budgets *this call*, not the farm's lifetime.
        Returns the device steps taken.
        """
        taken = 0
        while taken < max_device_steps and not (until is not None and until()):
            t = self.step(max_chunk=max_device_steps - taken)
            taken += t
            if not t:
                if self.table.n_active == 0 and self.table.n_queued:
                    # a zero-step round with work still queued means the
                    # resident batch just failed out: keep admitting so
                    # every queued sim resolves (possibly also to "failed")
                    # instead of parking in the queue forever
                    continue
                break
        return taken

    def run_until_drained(self, max_device_steps: int = 100_000
                          ) -> dict[int, SimResult]:
        """Step until queue and slots are empty; returns all results."""
        self.run(max_device_steps)
        return self.results

    # -- eviction (service hook) ---------------------------------------------
    def evict(self, sid: int) -> tuple[SimRequest, dict, int] | None:
        """Pull a *running* simulation off the device mid-flight.

        Returns ``(request, host_state, steps_done)`` and frees the slot;
        None if ``sid`` is not currently resident.  Readmission goes through
        ``submit`` with ``init_state``/``step0`` set (see the service).
        """
        for slot, entry in self.table.occupied():
            if entry.req.sid == sid:
                with self.tel.section("farm.evict"):
                    state = self.exec.read_slot(slot)
                self._live.discard(sid)
                self.table.release(slot)
                self.exec.clear_slot(slot)
                if self.monitor is not None:
                    self.monitor.release(sid)
                if self.tel.enabled:
                    self.tel.metrics.inc("sim.evictions")
                    self.tel.trace.emit("evict", sid=sid, farm=self.farm_id,
                                        slot=slot,
                                        steps_done=entry.steps_done)
                    self._gauge_load()
                return entry.req, state, entry.steps_done
        return None

    def known(self, sid: int) -> bool:
        """Has this sid ever been issued by the farm?"""
        return 0 <= sid < self._next_sid

    def steps_done(self, sid: int) -> int | None:
        for _, entry in self.table.occupied():
            if entry.req.sid == sid:
                return entry.steps_done
        return None

    def health_snapshot(self) -> dict:
        """One dashboard frame: farm id, device step, queue depth, and a
        fixed-order per-slot row (free slots included) with each resident
        sim's latest health frame when monitoring is on.  Rendered by
        ``repro.obs.health.render_dashboard`` / ``Runtime.watch``."""
        slots = []
        for slot, entry in enumerate(self.table.slots()):
            if entry is None or not isinstance(entry, _SlotEntry):
                slots.append({"slot": slot, "sid": None})
                continue
            row = {"slot": slot, "sid": entry.req.sid, "tag": entry.req.tag,
                   "steps_done": entry.steps_done, "steps": entry.req.steps}
            if self.monitor is not None:
                row["health"] = self.monitor.frame_of(entry.req.sid)
            slots.append(row)
        return {"farm": self.farm_id, "device_steps": self.device_steps,
                "queued": self.table.n_queued, "slots": slots,
                "states": (self.monitor.counts()
                           if self.monitor is not None else {})}
