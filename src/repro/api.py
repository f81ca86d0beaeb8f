"""repro.api — one runtime front door for driver, farm, and decomposed runs.

The Cactus "flesh" surface of this repo: applications declare *what* to run
(a registered :class:`~repro.sim.scenarios.Scenario` + per-run parameters)
and a :class:`RuntimeConfig` declares *where/how* (resolution, mesh axes,
per-slot grid decomposition, kernel backend, checkpointing); the
:class:`Runtime` derives the execution stack — a serial
``GridDriver``-jitted step, a slot-parallel ``SimulationFarm``, or the full
slots × shards ``SimulationService`` — behind two verbs:

    rt = repro.api.runtime(n=32)
    res = rt.run("cavity", t_end=5.0, re=100.0)       # one run, blocking
    sid = rt.submit("cavity", steps=400, re=250.0)    # farm intake
    rt.result(sid)                                    # ... submit/poll/result

The migration contract (frozen by ``tests/test_api.py``): everything the
Runtime resolves is *bitwise identical* to hand-assembling the legacy
constructor stack (``NavierStokes3D`` + ``make_step`` loops,
``SimulationFarm``/``SimulationService``) — the front door adds routing,
never numerics.  The legacy constructors remain importable and supported
for one release; new code should not need them.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Any, Callable, Mapping

import jax
import jax.numpy as jnp

from repro import obs
from repro.cfd.ns3d import CFDConfig, NavierStokes3D
from repro.core.schedule import Schedule
from repro.sim.ensemble import plan_decomposition
from repro.sim.farm import SimResult, static_key
from repro.sim.scenarios import (
    ParamSpec, Scenario, UnknownScenarioError, get_scenario,
    register_scenario, scenario_names, unregister_scenario,
)
from repro.sim.service import SimulationService

__all__ = [
    "BACKENDS", "ParamSpec", "PreparedRun", "RunResult", "Runtime",
    "RuntimeConfig", "Scenario", "SimResult", "UnknownScenarioError",
    "compile_cache_stats", "get_scenario", "register_scenario", "runtime",
    "scenario_names", "unregister_scenario",
]

# backend name -> (CFDConfig.template, CFDConfig.interpret, overlap override)
# The 3DBLOCK template is the monolithic tiled kernel: it needs
# tile-divisible interiors, so the Pallas backends disable the
# interior/boundary overlap split (a JNP-path optimization whose deep
# interior is never tile-aligned).  Tiles are chip-aware roofline choices
# (autotune.tile_for) resolved per local interior, so any grid the
# autotuner can divide runs without hand-tuned TILE constants.
# Every backend serves every execution path — serial, slot-parallel farm,
# and slots × shards: per-simulation scalars reach the Pallas kernels
# through the generator's scalar-table operand (scalar prefetch on real
# TPU), so farm runs under "pallas"/"pallas-interpret" share one compiled
# kernel across heterogeneous slots and match "jnp" farms to tolerance
# (and pallas-interpret serial runs to float32 ulps).
# "auto" resolves to "jnp" on every host: the 3DBLOCK template does not
# lower for the TPU yet, and an explicit "pallas" there fails with the
# compiler's error (serially) or as terminated="failed" farm results —
# it never falls back to the interpreter or the jnp oracle.
BACKENDS = {
    "jnp": ("JNP", False, None),            # fused-XLA template (CPU/TPU)
    "pallas-interpret": ("3DBLOCK", True, False),  # Pallas tiles, interpret
    "pallas": ("3DBLOCK", False, False),    # Pallas tiles on real hardware
    # jnp until tests/test_chip_compile.py::test_3dblock_jacobi_compiles
    "auto": ("JNP", False, None),
}


def compile_cache_stats() -> dict:
    """Process-wide ensemble-step compile cache stats (re-export)."""
    from repro.sim.farm import compile_cache_stats as _stats

    return _stats()


@dataclasses.dataclass(frozen=True)
class RuntimeConfig:
    """Everything the runtime needs to resolve an execution stack.

    ``mesh_shape``/``mesh_axes`` name the device mesh (built lazily; an
    empty shape means single-device).  ``decomposition`` maps grid axes to
    mesh axes for per-slot/per-run domain decomposition — validation and
    the extent-1 degrade follow the farm's ``plan_decomposition`` rules,
    so a laptop mesh and a pod fail (or degrade) identically.  ``solver``
    carries static solver overrides (``jacobi_iters``, ``fused_sweeps``,
    ``overlap``, ...) applied to every scenario config this runtime
    builds.
    """

    n: int = 32                          # grid resolution (n, n, nz)
    nz: int | None = None                # None -> scenario default
    backend: str = "jnp"                 # see BACKENDS
    mesh_shape: tuple = ()               # e.g. (2, 4)
    mesh_axes: tuple = ()                # e.g. ("slot", "shard")
    slot_axis: str = "slot"              # farm slot axis when meshed
    decomposition: tuple = ()            # e.g. ((0, "shard"),)
    n_slots: int = 4                     # farm slots per service
    ckpt_dir: str | None = None          # eviction spill directory
    check_every: int = 16                # convergence-check interval
    solver: Mapping[str, Any] = dataclasses.field(default_factory=dict)
    # observability: False (default, bitwise-invisible), True, a
    # repro.obs.TelemetryConfig / Telemetry, or a TelemetryConfig kwargs
    # dict ({"trace_path": ...}); see repro.obs.resolve
    telemetry: Any = False
    # in-situ health monitoring + NaN quarantine on the farm path: False
    # (default: the pre-health executable, nothing compiled in), True, a
    # repro.obs.HealthConfig, or a HealthConfig kwargs dict
    # ({"div_diverged": 1e6, "flight_dir": ...}); flight records default
    # under <ckpt_dir>/flight when a checkpoint dir is set.  Independent
    # of `telemetry` — quarantine is functional, not instrumentation.
    health: Any = False
    # durable job engine (repro.jobs): None (default, the in-memory path,
    # bitwise-invisible), a repro.jobs.JobStore, True (jobs.sqlite under
    # ckpt_dir), a sqlite path string, or a JobStore kwargs dict
    # ({"path": ..., "ttl_s": ...}); see repro.jobs.resolve_store.  With a
    # store, submits are durable before admission, a restarted Runtime
    # resumes incomplete work first, and several processes share one
    # queue via leases.
    store: Any = None

    def __post_init__(self):
        if self.backend not in BACKENDS:
            raise ValueError(f"unknown backend {self.backend!r} "
                             f"(have {sorted(BACKENDS)})")
        if bool(self.mesh_shape) != bool(self.mesh_axes) or \
                len(self.mesh_shape) != len(self.mesh_axes):
            raise ValueError(
                f"mesh_shape {self.mesh_shape!r} and mesh_axes "
                f"{self.mesh_axes!r} must pair up axis-for-axis")


@dataclasses.dataclass
class RunResult:
    """A finished single run: host-visible state + schedule diagnostics."""

    scenario: str
    state: dict
    steps_done: int
    terminated: str              # "steps" | "residual" | "steady"
    config: CFDConfig
    diagnostics: dict


@dataclasses.dataclass
class PreparedRun:
    """A resolved-but-not-run single simulation: the solver, its schedule,
    the initial state (INITIAL bin output) and the jitted EVOLVE step.
    The escape hatch for benchmarks and custom drive loops that need the
    raw step function while still resolving everything through the
    runtime."""

    scenario: Scenario
    solver: NavierStokes3D
    schedule: Schedule
    state: dict
    step: Callable[[dict], dict]
    config: CFDConfig

    def analyze(self, state: dict, steps_done: int = 0) -> dict:
        ctx = {"t": steps_done * self.config.dt, "steps": steps_done}
        return self.scenario.analyze(self.solver, state, ctx)


def _residual_norm(new: dict, old: dict, dt) -> jnp.ndarray:
    """``||u_new - u_old||_inf / dt`` over the velocity fields (the serial
    twin of ``EnsembleExecutor.residuals``)."""
    m = jnp.max(jnp.stack([jnp.max(jnp.abs(new[f] - old[f]))
                           for f in ("vx", "vy", "vz")]))
    return m / jnp.maximum(dt, 1e-30)


_residual_norm_jit = jax.jit(_residual_norm)


class Runtime:
    """The front door: resolves scenarios against one RuntimeConfig.

    Single runs (``run``/``prepare``) build the serial ``GridDriver``
    stack — decomposed over the mesh's shard axes when the config asks
    for it.  Ensemble traffic (``submit``/``poll``/``result``/``drain``)
    routes through ``SimulationService`` farms, one per static signature,
    created lazily on first submit; a signature whose stack fails to
    build (e.g. an indivisible decomposition) resolves its sids to
    ``terminated="failed"`` results instead of wedging the queue.
    """

    def __init__(self, config: RuntimeConfig | None = None,
                 mesh: jax.sharding.Mesh | None = None):
        self.config = config if config is not None else RuntimeConfig()
        # one telemetry handle per runtime: every service/farm this
        # runtime resolves reports into it (scoped compile-cache stats,
        # farm metrics, per-sim traces); NULL when disabled, making every
        # hook a no-op on the default path
        self.telemetry = obs.resolve(self.config.telemetry)
        health = obs.resolve_health(self.config.health)
        if (health is not None and health.flight_dir is None
                and self.config.ckpt_dir is not None):
            health = dataclasses.replace(
                health,
                flight_dir=os.path.join(self.config.ckpt_dir, "flight"))
        self.health = health
        self._mesh = mesh                  # explicit mesh wins over shape
        self._mesh_built = mesh is not None
        self._services: dict[tuple, SimulationService] = {}
        self._routes: dict[int, tuple[SimulationService, int]] = {}
        self._failed: dict[int, SimResult] = {}
        self._scenario_of: dict[int, str] = {}
        # latest PreparedRun per scenario, kept only under telemetry so
        # perf accounting can re-lower the serial EVOLVE bin; the off path
        # pins no extra field state
        self._prepared: dict[str, PreparedRun] = {}
        self._next_sid = 0
        from repro.jobs import resolve_store

        self.store = resolve_store(self.config.store, self.config.ckpt_dir)
        # job_ids this process admitted itself: a claim must never return
        # our own job whose lease briefly expired (a long compile between
        # heartbeats) — that would double-admit it locally
        self._jobs_local: set[int] = set()
        if self.store is not None:
            # the restart contract: orphaned in-flight work resumes FIRST,
            # before any claim() touches the queued backlog
            self.recover()

    # -- resolution -----------------------------------------------------------
    @property
    def mesh(self) -> jax.sharding.Mesh | None:
        if not self._mesh_built:
            if self.config.mesh_shape:
                from repro.launch.mesh import make_mesh

                self._mesh = make_mesh(tuple(self.config.mesh_shape),
                                       tuple(self.config.mesh_axes))
            self._mesh_built = True
        return self._mesh

    def configure(self, scenario, n: int | None = None, **kw) -> CFDConfig:
        """The fully-resolved CFDConfig for ``scenario`` under this
        runtime: scenario builder -> static solver overrides -> backend
        template -> decomposition.  ``n`` overrides the runtime's default
        resolution (a different static signature, hence — on the farm
        path — a different lazily-built service)."""
        sc = get_scenario(scenario)
        template, interpret, overlap = BACKENDS[self.config.backend]
        builder_kw = dict(self.config.solver)
        if self.config.nz is not None:
            builder_kw["nz"] = self.config.nz
        builder_kw.update(kw)
        cfg = sc.config(self.config.n if n is None else n, **builder_kw)
        return dataclasses.replace(
            cfg, template=template, interpret=interpret,
            overlap=cfg.overlap if overlap is None else overlap,
            decomposition=tuple(self.config.decomposition) or
            cfg.decomposition)

    def prepare(self, scenario, n: int | None = None,
                **params) -> PreparedRun:
        """Resolve one serial run: solver (+ decomposition over the mesh's
        shard axes), schedule, INITIAL state, jitted EVOLVE step."""
        sc = get_scenario(scenario)
        builder_kw, ic_kw = sc.split_kwargs(params)
        cfg = self.configure(sc, n=n, **builder_kw)
        # identical resolution rules to the farm: validate against the
        # mesh, drop extent-1 axes, run meshless when nothing decomposes
        solver_cfg, active = plan_decomposition(
            cfg, self.mesh,
            slot_axis=self.config.slot_axis if self.mesh is not None and
            self.config.slot_axis in self.mesh.axis_names else None)
        solver = NavierStokes3D(solver_cfg, self.mesh if active else None)
        sched = sc.schedule(solver, ic=ic_kw)
        tel = self.telemetry if self.telemetry.enabled else None
        state = sched.compile_bin("INITIAL", telemetry=tel)({})
        evolve = sched.compile_bin("EVOLVE", telemetry=tel)
        # a decomposed step's halo traffic per device, the sweeps that
        # still build a padded copy of p (a stat only where there are
        # any: 3DBLOCK, the fused smoother) and those that stream p
        # through VMEM (only on a TPU), counted once here from the plan
        # and carried by every step's span as its stats
        from repro.obs import perf

        stats = {}
        if active:
            stats = dict(
                halo_bytes=perf.halo_bytes_per_step(
                    solver_cfg, active, dict(self.mesh.shape)),
                halo_permutes=perf.halo_permutes_per_step(solver_cfg,
                                                          active))
        padded = perf.padded_sweeps_per_step(solver_cfg)
        if padded:
            stats["padded_sweeps"] = padded
        device = (self.mesh.devices.flat[0] if active else jax.devices()[0])
        streamed = perf.streamed_sweeps_per_step(
            solver_cfg, solver.driver.local_shape, device.platform)
        if streamed:
            stats["streamed_sweeps"] = streamed

        def step(st: dict) -> dict:
            with obs.span("runtime.step", **stats):
                return evolve(st)

        pr = PreparedRun(scenario=sc, solver=solver, schedule=sched,
                         state=state, step=step, config=cfg)
        if self.telemetry.enabled:
            self._prepared[sc.name] = pr
        return pr

    # -- single-run drive -----------------------------------------------------
    def run(self, scenario, *, n: int | None = None,
            steps: int | None = None,
            t_end: float | None = None, residual_tol: float | None = None,
            steady_tol: float | None = None, progress: int | None = None,
            **params) -> RunResult:
        """Run one simulation to completion, blocking.

        Termination: ``steps``/``t_end`` bound the run; ``residual_tol``
        additionally stops at steady state once
        ``||u^{n+1} - u^n||_inf / dt`` falls below it (checked every
        ``RuntimeConfig.check_every`` steps); ``steady_tol`` is the legacy
        kinetic-energy-drift heuristic.  The step sequence is bitwise the
        legacy ``make_step`` loop — convergence checks read snapshots,
        they never perturb the state path.
        """
        pr = self.prepare(scenario, n=n, **params)
        cfg = pr.config
        if steps is None:
            if t_end is None:
                raise ValueError("give either steps= or t_end=")
            steps = int(round(t_end / cfg.dt))
        check = max(int(self.config.check_every), 1)
        state, terminated, done = pr.state, "steps", 0
        ke_prev: float | None = None
        with self.telemetry.timers.section(f"run.{pr.scenario.name}"):
            for i in range(steps):
                # snapshot only when this step lands on a residual check
                # boundary — an unconditional snapshot would pin a second
                # full field state for the whole run
                prev = state if (residual_tol is not None
                                 and (i + 1) % check == 0) else None
                state, last = pr.step(state), state
                # wait for the previous step once this one is queued: each
                # queued step holds its own output state, and unbounded
                # run-ahead fills the device (16 GB after 400 steps at
                # 256^3 on a v5e) without making the run any faster
                jax.block_until_ready(last)
                done = i + 1
                if progress and (done % progress == 0):
                    print(f"  step {done:6d}/{steps} "
                          f"t={done * cfg.dt:8.3f} "
                          f"KE={pr.solver.kinetic_energy(state):.6f}")
                if residual_tol is not None and done % check == 0:
                    resid = float(_residual_norm_jit(state, prev,
                                                     jnp.float32(cfg.dt)))
                    if resid <= residual_tol:
                        terminated = "residual"
                        break
                if steady_tol is not None and done % check == 0:
                    ke = pr.solver.kinetic_energy(state)
                    if ke_prev is not None and abs(ke - ke_prev) <= \
                            steady_tol * max(abs(ke), 1e-12):
                        terminated = "steady"
                        break
                    ke_prev = ke
        if self.telemetry.enabled:
            self.telemetry.metrics.inc("sim.steps_total", done)
        diagnostics = pr.analyze(state, done)
        return RunResult(scenario=pr.scenario.name,
                         state=jax.device_get(state), steps_done=done,
                         terminated=terminated, config=cfg,
                         diagnostics=diagnostics)

    # -- ensemble / service routing -------------------------------------------
    def _service_for(self, cfg: CFDConfig
                     ) -> tuple[SimulationService | None, str | None]:
        key = static_key(cfg, self.config.n_slots)
        if key in self._services:
            return self._services[key], None
        ckpt = None
        if self.config.ckpt_dir is not None:
            # one spill directory per signature: service-local sids double
            # as checkpoint step ids and must not collide across farms
            ckpt = os.path.join(self.config.ckpt_dir,
                                f"sig{len(self._services):03d}")
        try:
            svc = SimulationService(
                cfg, n_slots=self.config.n_slots, ckpt_dir=ckpt,
                check_steady_every=self.config.check_every,
                mesh=self.mesh, slot_axis=self.config.slot_axis,
                telemetry=self.telemetry, health=self.health,
                farm_id=f"{cfg.case}/sig{len(self._services):03d}",
                store=self.store)
        except Exception as e:
            return None, f"{type(e).__name__}: {e}"
        self._services[key] = svc
        return svc, None

    def submit(self, scenario, *, n: int | None = None,
               steps: int | None = None,
               t_end: float | None = None, tag: str = "",
               steady_tol: float | None = None,
               residual_tol: float | None = None, priority: int = 0,
               **params) -> int:
        """Queue one simulation on the farm; returns its sid.

        Requests of an unseen static signature lazily build their
        ``SimulationService``; a signature whose stack cannot build
        resolves this sid to a ``terminated="failed"`` result (surfaced
        by ``poll``/``result``/``drain``) rather than raising into the
        submit path or blocking a later drain.
        """
        sc = get_scenario(scenario)
        builder_kw, ic_kw = sc.split_kwargs(params)
        cfg = self.configure(sc, n=n, **builder_kw)
        req = sc.request(
            self.config.n if n is None else n, config=cfg,
            steps=steps, t_end=t_end, tag=tag,
            steady_tol=steady_tol, residual_tol=residual_tol,
            priority=priority, **ic_kw)
        sid = self._next_sid
        self._next_sid += 1
        self._scenario_of[sid] = sc.name
        svc, err = self._service_for(cfg)
        if svc is None:
            if self.store is not None:
                # even a sim whose stack cannot build leaves a durable
                # audit row — submitted, failed, never silently dropped
                from repro import jobs

                jid = self.store.submit(
                    req, signature=str(static_key(cfg, self.config.n_slots)),
                    lease=True)
                self.store.transition(jid, jobs.FAILED, error=err,
                                      event="result")
                self._jobs_local.add(jid)
            self._failed[sid] = SimResult(
                sid=sid, tag=req.tag, steps_done=0, terminated="failed",
                state={}, config=cfg, error=err)
            return sid
        inner = svc.submit(req)
        self._routes[sid] = (svc, inner)
        jid = svc.job_of(inner)
        if jid is not None:
            self._jobs_local.add(jid)
        return sid

    def poll(self, sid: int) -> dict:
        if sid in self._failed:
            res = self._failed[sid]
            return {"status": "failed", "steps_done": 0, "error": res.error}
        if sid not in self._routes:
            raise KeyError(f"unknown simulation id {sid}")
        svc, inner = self._routes[sid]
        return svc.poll(inner)

    def result(self, sid: int, block: bool = True) -> SimResult:
        if sid in self._failed:
            res = self._failed[sid]
            raise RuntimeError(
                f"simulation {sid} ({res.tag or 'untagged'}) failed: "
                f"{res.error}")
        if sid not in self._routes:
            raise KeyError(f"unknown simulation id {sid}")
        svc, inner = self._routes[sid]
        return dataclasses.replace(svc.result(inner, block=block), sid=sid)

    def evict(self, sid: int) -> bool:
        if sid not in self._routes:
            return False
        svc, inner = self._routes[sid]
        return svc.evict(inner)

    def readmit(self, sid: int) -> bool:
        if sid not in self._routes:
            return False
        svc, inner = self._routes[sid]
        return svc.readmit(inner)

    # -- durable jobs (repro.jobs) ---------------------------------------------
    def _job_gauges(self):
        if self.store is None or not self.telemetry.enabled:
            return
        self.telemetry.metrics.set("jobs.lease_takeovers",
                                   self.store.takeovers)
        self.telemetry.metrics.set("jobs.store_queue_depth",
                                   self.store.queue_depth())

    def _admit_job(self, job, resumed: bool = False) -> int:
        """Admit one claimed store row into this process's farms,
        resuming from its latest eviction snapshot when asked."""
        from repro import jobs

        req = job.request()
        if resumed:
            snap = self.store.latest_snapshot(job.job_id, "evict")
            if snap is not None and snap["fields"]:
                # resume pointer: re-enter a slot bitwise at the snapshot
                steps_done, state = self.store.load_snapshot(job.job_id,
                                                             "evict")
                req = dataclasses.replace(req, init_state=state,
                                          step0=steps_done)
            # no snapshot: the job was claimed before ever reaching a
            # spill point — it restarts from its payload (step0 intact)
        sid = self._next_sid
        self._next_sid += 1
        self._jobs_local.add(job.job_id)
        svc, err = self._service_for(req.config)
        if svc is None:
            self.store.transition(job.job_id, jobs.FAILED, error=err,
                                  event="result")
            self._failed[sid] = SimResult(
                sid=sid, tag=req.tag, steps_done=0, terminated="failed",
                state={}, config=req.config, error=err)
            return sid
        try:
            inner = svc.submit(req, job_id=job.job_id)
        except Exception as e:
            # service.submit already transitioned the row to failed
            self._failed[sid] = SimResult(
                sid=sid, tag=req.tag, steps_done=0, terminated="failed",
                state={}, config=req.config,
                error=f"{type(e).__name__}: {e}")
            return sid
        self._routes[sid] = (svc, inner)
        return sid

    def enqueue(self, scenario, *, n: int | None = None,
                steps: int | None = None, t_end: float | None = None,
                tag: str = "", steady_tol: float | None = None,
                residual_tol: float | None = None, priority: int = 0,
                **params) -> int:
        """Queue one simulation durably WITHOUT admitting it here;
        returns its store job_id.  The detached half of ``submit``: any
        process sharing the store — this one included — picks it up via
        ``claim()``/``drain()``, so a front-end process can feed worker
        processes through nothing but the store file."""
        if self.store is None:
            raise RuntimeError(
                "enqueue() needs a job store — RuntimeConfig(store=...)")
        sc = get_scenario(scenario)
        builder_kw, ic_kw = sc.split_kwargs(params)
        cfg = self.configure(sc, n=n, **builder_kw)
        req = sc.request(
            self.config.n if n is None else n, config=cfg,
            steps=steps, t_end=t_end, tag=tag,
            steady_tol=steady_tol, residual_tol=residual_tol,
            priority=priority, **ic_kw)
        job_id = self.store.submit(
            req, signature=str(static_key(cfg, self.config.n_slots)),
            lease=False)
        if self.telemetry.enabled:
            self.telemetry.trace.emit("job_enqueue", job_id=job_id, tag=tag)
        self._job_gauges()
        return job_id

    def claim(self, max_jobs: int | None = None) -> list[int]:
        """Lease up to ``max_jobs`` queued store jobs (default: one
        farm's worth) and admit them locally; returns their sids.  Jobs
        this process already admitted are never re-claimed, even if their
        lease briefly lapsed."""
        if self.store is None:
            return []
        limit = max_jobs if max_jobs is not None else self.config.n_slots
        claimed = [j for j in self.store.claim(limit=limit)
                   if j.job_id not in self._jobs_local]
        sids = [self._admit_job(j) for j in claimed]
        if self.telemetry.enabled:
            for j in claimed:
                self.telemetry.trace.emit("job_claim", job_id=j.job_id,
                                          tag=j.tag)
        self._job_gauges()
        return sids

    def recover(self, limit: int = 64) -> list[int]:
        """Claim orphaned in-flight jobs (``running``/``evicted`` rows
        with an expired lease — their process died) and readmit each from
        its latest snapshot.  Runs automatically when a store-configured
        Runtime is built, BEFORE any queued work is claimed — the
        restart-resumes-incomplete-first contract."""
        if self.store is None:
            return []
        claimed = [j for j in self.store.claim_incomplete(limit=limit)
                   if j.job_id not in self._jobs_local]
        sids = [self._admit_job(j, resumed=True) for j in claimed]
        if self.telemetry.enabled:
            if claimed:
                self.telemetry.metrics.inc("jobs.resumed", len(claimed))
            for j in claimed:
                self.telemetry.trace.emit("job_resume", job_id=j.job_id,
                                          tag=j.tag, status=j.status)
        self._job_gauges()
        return sids

    def job_of(self, sid: int) -> int | None:
        """The durable job_id behind a sid (None without a store)."""
        if sid not in self._routes:
            return None
        svc, inner = self._routes[sid]
        return svc.job_of(inner)

    def jobs(self, status=None):
        """Store job rows (optionally filtered by status)."""
        if self.store is None:
            return []
        return self.store.jobs(status)

    def load_result(self, job_id: int) -> dict:
        """A done job's persisted final field state, from any process."""
        if self.store is None:
            raise RuntimeError("load_result() needs a job store")
        return self.store.load_result(job_id)

    def flight_record(self, job_id: int):
        """The flight record of a diverged job, resolved through its
        store registration — works after a process restart, when the
        farm that recorded it is long gone."""
        from repro.obs.health import load_flight_record

        snap = (self.store.latest_snapshot(job_id, "flight")
                if self.store is not None else None)
        if snap is None:
            raise KeyError(f"job {job_id} has no registered flight record")
        return load_flight_record(snap["dir"], snap["step_key"])

    def drain(self, max_device_steps: int = 100_000) -> dict[int, SimResult]:
        """Run every farm dry; ALWAYS returns one result per submitted
        sid, failed sims included (``terminated="failed"`` + error).
        With a job store, also keeps claiming queued store jobs until the
        shared queue is empty (or every remaining job is leased by a live
        peer), so ``drain`` on any worker drives the whole backlog."""
        while self.store is not None and self.claim():
            for svc in self._services.values():
                svc.drain(max_device_steps)
        for svc in self._services.values():
            svc.drain(max_device_steps)
        out: dict[int, SimResult] = {}
        for sid, (svc, inner) in self._routes.items():
            res = svc.farm.results.get(inner)
            if res is not None:
                out[sid] = dataclasses.replace(res, sid=sid)
        out.update(self._failed)
        return out

    def analyze(self, result: SimResult | RunResult) -> dict:
        """Scenario ANALYSIS diagnostics for a finished farm result
        (matches RunResult.diagnostics for the equivalent single run)."""
        name = result.scenario if isinstance(result, RunResult) else \
            self._scenario_of.get(result.sid)
        if name is None:
            # foreign SimResult: match on the config's case string
            for cand in scenario_names():
                if get_scenario(cand).config(result.config.shape[0]).case \
                        == result.config.case:
                    name = cand
                    break
        if name is None:
            raise ValueError("cannot infer a scenario for this result")
        sc = get_scenario(name)
        solver = NavierStokes3D(
            dataclasses.replace(result.config, decomposition=()))
        ctx = {"t": result.steps_done * result.config.dt,
               "steps": result.steps_done}
        return sc.analyze(solver, result.state, ctx)

    def watch(self, refresh_s: float | None = None,
              iterations: int | None = None) -> str:
        """Live per-slot health dashboard over every resolved farm
        (Cactus-HTTPD style, as text).

        Called bare it renders and returns one frame — slot occupancy,
        per-sim progress, latest health state/diagnostics, queue depth.
        With ``refresh_s`` it also prints the frame and re-renders every
        ``refresh_s`` seconds until the farms go idle (or ``iterations``
        frames have printed), returning the last frame — run it from a
        second thread, or interleave with ``services()[i].run(...)``
        from a drive loop.
        """
        from repro.obs.health import render_dashboard

        def frame() -> str:
            return render_dashboard(
                [svc.farm.health_snapshot()
                 for svc in self._services.values()])

        if refresh_s is None:
            return frame()
        import time

        n, text = 0, frame()
        while True:
            text = frame()
            print(text, flush=True)
            n += 1
            if iterations is not None and n >= iterations:
                break
            if all(svc.farm.table.idle for svc in self._services.values()):
                break
            time.sleep(refresh_s)
        return text

    # -- introspection --------------------------------------------------------
    def device_steps(self) -> int:
        """Total device dispatch steps across every resolved farm."""
        return sum(svc.farm.device_steps for svc in self._services.values())

    def services(self) -> tuple[SimulationService, ...]:
        return tuple(self._services.values())

    def perf_report(self, chip="auto", dtype: str = "f32"):
        """Cost-model-grounded accounting of every executable this
        runtime compiled: one :class:`repro.obs.perf.PerfReport` row per
        farm signature and prepared serial scenario, with predicted
        FLOPs / HBM bytes / collective wire bytes joined against the
        measured timer sections (see ``repro.obs.perf``)."""
        from repro.obs import perf

        return perf.report_for_runtime(self, chip=chip, dtype=dtype)

    def report(self, perf: bool = False, chip="auto") -> str:
        """This runtime's ``repro.obs.report()`` (timers + metrics);
        ``perf=True`` appends the roofline-attributed perf accounting."""
        text = obs.report(self.telemetry)
        if perf:
            text += "\n" + self.perf_report(chip=chip).render()
        return text


def runtime(n: int = 32, *, backend: str = "jnp", mesh_shape: tuple = (),
            mesh_axes: tuple = (), decomposition: tuple = (),
            slot_axis: str = "slot", n_slots: int = 4,
            ckpt_dir: str | None = None, check_every: int = 16,
            nz: int | None = None, mesh: jax.sharding.Mesh | None = None,
            telemetry: Any = False, health: Any = False, store: Any = None,
            **solver) -> Runtime:
    """Build a :class:`Runtime` — the one-call front door.

    >>> rt = repro.api.runtime(n=32, telemetry=True, health=True)
    >>> res = rt.run("cavity", t_end=5.0, re=100.0)
    >>> res.diagnostics["ghia"]
    >>> print(rt.report())        # Cactus-style timers + farm metrics
    >>> print(rt.watch())         # live per-slot health dashboard
    """
    cfg = RuntimeConfig(n=n, nz=nz, backend=backend,
                        mesh_shape=tuple(mesh_shape),
                        mesh_axes=tuple(mesh_axes),
                        decomposition=tuple(decomposition),
                        slot_axis=slot_axis, n_slots=n_slots,
                        ckpt_dir=ckpt_dir, check_every=check_every,
                        solver=dict(solver), telemetry=telemetry,
                        health=health, store=store)
    return Runtime(cfg, mesh=mesh)
