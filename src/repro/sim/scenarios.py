"""Scenario registry — the Cactus "thorn list" for the simulation runtime.

Cactus applications are assemblies: physics *thorns* declare their grid
functions, parameters, and schedule-bin routines, and the flesh derives
everything else (storage, halo exchange, placement, execution order).  A
:class:`Scenario` is this repo's thorn descriptor: it names a problem
(config builder + parameter schema), optionally supplies an initial-condition
routine and analysis routines, and wires them into the
:class:`repro.core.schedule.Schedule` bins —

    INITIAL    allocate fields + apply the scenario's IC
    EVOLVE     the solver step (alias of the Cactus EVOL bin)
    ANALYSIS   diagnostics computed on demand over a finished state

``@register_scenario`` puts a scenario into the process-wide registry so
:mod:`repro.api` can resolve it by name; third-party code registers its own
scenarios exactly the way the built-ins below do (``kelvin_helmholtz`` is
deliberately written as such a "third-party" thorn: the solver knows only
its periodicity, the scenario owns the shear-layer IC).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Mapping

import jax.numpy as jnp
import numpy as np

from repro.cfd.ns3d import PLATE_T, CFDConfig, NavierStokes3D
from repro.core.schedule import Schedule


class UnknownScenarioError(KeyError):
    """Raised when resolving a scenario name that was never registered."""


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    """One entry of a scenario's parameter schema (PARAM_KEYS-style):
    a default plus a one-line doc, so the front door can list and
    validate per-run parameters without knowing any physics."""

    default: float
    doc: str = ""


@dataclasses.dataclass(frozen=True)
class Scenario:
    """A registered problem: config builder, parameter schema, IC, analyses.

    ``builder(n, **kw)`` returns the :class:`CFDConfig`; runtime parameters
    (``params`` schema — Reynolds number, viscosity, lid velocity, ...)
    are builder keyword arguments, while ``ic_params`` shape only the
    initial condition (``init_fields``) and never enter the config.
    ``analyses`` maps a diagnostic name to ``fn(solver, state, ctx)``
    where ``ctx`` carries ``{"t", "steps"}``.
    """

    name: str
    description: str
    builder: Callable[..., CFDConfig]
    params: Mapping[str, ParamSpec] = dataclasses.field(default_factory=dict)
    ic_params: Mapping[str, ParamSpec] = dataclasses.field(
        default_factory=dict)
    init_fields: Callable[..., dict] | None = None
    analyses: Mapping[str, Callable] = dataclasses.field(default_factory=dict)

    # -- parameter plumbing ---------------------------------------------------
    def split_kwargs(self, kw: Mapping[str, Any]) -> tuple[dict, dict]:
        """Split mixed per-run kwargs into ``(builder_kw, ic_kw)``.

        IC-schema keys go to ``init_fields`` (with defaults filled in);
        everything else — runtime parameters and static solver knobs
        (``jacobi_iters``, ``dt``, ...) — flows to the builder, whose
        :class:`CFDConfig` constructor rejects unknown names.
        """
        kw = dict(kw)
        ic = {k: v.default for k, v in self.ic_params.items()}
        for k in list(kw):
            if k in self.ic_params:
                ic[k] = kw.pop(k)
        return kw, ic

    def config(self, n: int = 32, **kw) -> CFDConfig:
        """The scenario's :class:`CFDConfig` at resolution ``n``."""
        builder_kw, _ = self.split_kwargs(kw)
        return self.builder(n, **builder_kw)

    # -- schedule wiring ------------------------------------------------------
    def initial_state(self, solver: NavierStokes3D, **ic_kw) -> dict:
        """INITIAL bin, as a plain call: allocate + scenario IC."""
        return self.schedule(solver, ic=ic_kw).compile_bin("INITIAL")({})

    def schedule(self, solver: NavierStokes3D, step_fn: Callable | None = None,
                 ic: Mapping[str, Any] | None = None) -> Schedule:
        """The scenario's schedule tree against a concrete solver.

        INITIAL composes field allocation with the scenario IC (ordered
        AFTER allocation); EVOLVE holds the solver step (``step_fn``
        defaults to ``solver.make_step()`` — pass the farm's step to share
        a compiled executable); ANALYSIS entries accumulate diagnostics
        into ``state["diagnostics"]`` reading run context from
        ``state["_ctx"]``.
        """
        _, ic_kw = self.split_kwargs(dict(ic or {}))
        sched = Schedule()
        sched.register("INITIAL", "allocate_fields")(
            lambda _state: solver.init_state())
        if self.init_fields is not None:
            sched.register("INITIAL", f"ic_{self.name}",
                           after=("allocate_fields",))(
                lambda state: self.init_fields(solver, state, **ic_kw))
        if step_fn is None:
            # build the jitted step on first use, so running only the
            # INITIAL or ANALYSIS bin never pays for an EVOLVE trace
            cache: list = []

            def step_fn(state):
                if not cache:
                    cache.append(solver.make_step())
                return cache[0](state)
        sched.register("EVOLVE", "ns3d_step")(step_fn)
        for diag_name, fn in self.analyses.items():
            def entry(state, fn=fn, diag_name=diag_name):
                diags = dict(state.get("diagnostics", {}))
                diags[diag_name] = fn(solver, state, state.get("_ctx", {}))
                return dict(state, diagnostics=diags)
            sched.register("ANALYSIS", diag_name)(entry)
        return sched

    def analyze(self, solver: NavierStokes3D, state: dict,
                ctx: Mapping[str, Any] | None = None) -> dict:
        """Run the ANALYSIS bin over ``state``; returns the diagnostics."""
        st = dict(state, _ctx=dict(ctx or {}), diagnostics={})
        return self.schedule(solver).compile_bin("ANALYSIS")(st)["diagnostics"]

    # -- farm intake ----------------------------------------------------------
    def request(self, n: int = 32, *, steps: int | None = None,
                t_end: float | None = None, tag: str = "",
                steady_tol: float | None = None,
                residual_tol: float | None = None, priority: int = 0,
                config: CFDConfig | None = None, **kw):
        """A :class:`~repro.sim.farm.SimRequest` for one run of this
        scenario.  When the scenario owns an IC, the initial fields are
        built host-side and ride in ``init_state`` (per-request ICs under
        one compiled step — a decomposed farm scatters them at admission).

        ``config`` short-circuits the builder with an already-resolved
        CFDConfig (the Runtime passes its fully-configured one, so step
        counts and the executed config can never drift apart); only
        IC-schema kwargs are honoured alongside it.
        """
        from repro.sim.farm import SimRequest   # lazy: avoid import cycle

        builder_kw, ic_kw = self.split_kwargs(kw)
        cfg = config if config is not None else self.builder(n, **builder_kw)
        if steps is None:
            if t_end is None:
                raise ValueError("give either steps= or t_end=")
            steps = int(round(t_end / cfg.dt))
        init_state = None
        if self.init_fields is not None:
            # the IC is built on an undecomposed host solver: admission
            # owns the scatter, so one request serves laptop and pod
            solver = NavierStokes3D(
                dataclasses.replace(cfg, decomposition=()))
            state = self.init_fields(solver, solver.init_state(), **ic_kw)
            init_state = {k: np.asarray(v) for k, v in state.items()}
        return SimRequest(config=cfg, steps=steps,
                          tag=tag or f"{self.name}-{n}",
                          steady_tol=steady_tol, residual_tol=residual_tol,
                          priority=priority, init_state=init_state)


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------
_REGISTRY: dict[str, Scenario] = {}


def register_scenario(obj=None, *, replace: bool = False):
    """Register a :class:`Scenario` — as a plain call, or as a decorator
    over a zero-argument factory function (the factory is invoked once at
    registration; the decorator returns the Scenario)."""
    def _register(scenario: Scenario) -> Scenario:
        if callable(scenario) and not isinstance(scenario, Scenario):
            scenario = scenario()
        if not isinstance(scenario, Scenario):
            raise TypeError(f"expected a Scenario, got {type(scenario)!r}")
        if scenario.name in _REGISTRY and not replace:
            raise ValueError(
                f"scenario {scenario.name!r} is already registered "
                "(pass replace=True to override)")
        _REGISTRY[scenario.name] = scenario
        return scenario

    if obj is None:             # @register_scenario(replace=...)
        return _register
    return _register(obj)       # @register_scenario / register_scenario(s)


def unregister_scenario(name: str) -> None:
    """Remove a registered scenario (tests / plugin teardown)."""
    _REGISTRY.pop(name, None)


def get_scenario(name) -> Scenario:
    """Resolve a scenario by name (a Scenario passes through unchanged)."""
    if isinstance(name, Scenario):
        return name
    try:
        return _REGISTRY[name]
    except KeyError:
        raise UnknownScenarioError(
            f"unknown scenario {name!r}; registered: "
            f"{sorted(_REGISTRY)}") from None


def scenario_names() -> tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


# ---------------------------------------------------------------------------
# built-in scenarios
# ---------------------------------------------------------------------------
def _cavity_builder(n: int = 32, **kw) -> CFDConfig:
    from repro.cfd import cavity

    return cavity.config(n, **kw)


def _cavity_ghia(solver, state, ctx):
    from repro.cfd import cavity

    return cavity.ghia_errors(solver, state)


def _cavity_centerline_u(solver, state, ctx):
    from repro.cfd import cavity

    return cavity.centerline_u(solver, state)


def _kinetic_energy(solver, state, ctx):
    return solver.kinetic_energy(state)


register_scenario(Scenario(
    name="cavity",
    description="Lid-driven cavity (z-periodic quasi-2D), validated "
                "against Ghia et al. (1982) centerline profiles",
    builder=_cavity_builder,
    params={"re": ParamSpec(100.0, "Reynolds number (sets nu = 1/re)"),
            "lid_velocity": ParamSpec(1.0, "lid speed in +x at the y-hi "
                                           "wall")},
    analyses={"ghia": _cavity_ghia,
              "centerline_u": _cavity_centerline_u,
              "kinetic_energy": _kinetic_energy},
))


def _tg_builder(n: int = 32, **kw) -> CFDConfig:
    from repro.cfd import taylor_green

    return taylor_green.config(n, **kw)


def _tg_error(solver, state, ctx):
    import jax

    from repro.cfd import taylor_green

    t = float(ctx.get("t", 0.0))
    ax, ay = taylor_green.analytic(solver, t)
    # both reductions in one fetch — per-value float() syncs twice and
    # blocks the ANALYSIS bin's dispatch
    ex, ey = jax.device_get((jnp.abs(state["vx"] - ax).max(),
                             jnp.abs(state["vy"] - ay).max()))
    return {"t": t, "err_vx": float(ex), "err_vy": float(ey)}


register_scenario(Scenario(
    name="taylor_green",
    description="Periodic Taylor-Green vortex with analytic decay "
                "(end-to-end solver validation)",
    builder=_tg_builder,
    params={"nu": ParamSpec(0.1, "kinematic viscosity (decay rate)")},
    analyses={"analytic_error": _tg_error,
              "kinetic_energy": _kinetic_energy},
))


# -- Kelvin-Helmholtz: the "third-party thorn" --------------------------------
def _kh_builder(n: int = 32, nz: int = 4, nu: float = 2e-3,
                dt: float | None = None, **kw) -> CFDConfig:
    h = 2.0 * math.pi / n
    dt = dt if dt is not None else min(0.2 * h, 0.2 * h * h / (6 * nu))
    kw.setdefault("jacobi_iters", 60)
    return CFDConfig(shape=(n, n, nz), extent=2.0 * math.pi, nu=nu, dt=dt,
                     case="kelvin_helmholtz", **kw)


def _kh_init(solver, state, *, delta: float, eps: float) -> dict:
    """Double shear layer on the periodic box [0, 2pi]^2 (z-invariant):
    vx = tanh across two interfaces at y = pi/2 and y = 3pi/2, seeded with
    a sinusoidal vy perturbation that triggers the roll-up.  Fields are
    sampled at their staggered face positions (see taylor_green.analytic).
    """
    x, y, _ = solver.driver.coords()
    vx = jnp.where(y < math.pi,
                   jnp.tanh((y - 0.5 * math.pi) / delta),
                   jnp.tanh((1.5 * math.pi - y) / delta))
    vy = eps * jnp.sin(x)
    return dict(state, vx=vx.astype(jnp.float32), vy=vy.astype(jnp.float32))


def _kh_amplitude(solver, state, ctx):
    """max |vy|: the instability amplitude (grows through roll-up)."""
    return float(jnp.abs(state["vy"]).max())


@register_scenario
def kelvin_helmholtz() -> Scenario:
    return Scenario(
        name="kelvin_helmholtz",
        description="Double shear layer on the periodic box: "
                    "Kelvin-Helmholtz roll-up from a seeded perturbation",
        builder=_kh_builder,
        params={"nu": ParamSpec(2e-3, "kinematic viscosity")},
        ic_params={"delta": ParamSpec(math.pi / 15, "shear layer width"),
                   "eps": ParamSpec(0.05, "vy perturbation amplitude")},
        init_fields=_kh_init,
        analyses={"amplitude": _kh_amplitude,
                  "kinetic_energy": _kinetic_energy},
    )


# -- Rayleigh-Benard: a temperature the scenario owns -------------------------
def _rb_builder(n: int = 48, nz: int | None = None, aspect: int = 6,
                ra: float = 1e6, pr: float = 0.7, dt: float | None = None,
                **kw) -> CFDConfig:
    """A layer ``aspect`` times as wide as deep, in free-fall units: the
    depth is 1 over ``n // aspect`` cubic cells, y and z are ``n`` and
    ``nz or n`` cells wide; ``ra`` and ``pr`` set nu and kappa."""
    nx = n // aspect
    h = 1.0 / nx
    nu, kappa = math.sqrt(pr / ra), 1.0 / math.sqrt(ra * pr)
    dt = dt if dt is not None else min(0.25 * h,
                                       0.2 * h * h / (6 * max(nu, kappa)))
    kw.setdefault("jacobi_iters", 60)
    return CFDConfig(shape=(nx, n, nz or n), extent=1.0, nu=nu, kappa=kappa,
                     dt=dt, case="rayleigh_benard", **kw)


# low modes (in y, in z) of the seeded temperature perturbation
_RB_MODES = ((1, 0), (0, 1), (1, 1), (2, 1), (1, 2))


def _rb_init(solver, state, *, seed: int, amplitude: float) -> dict:
    """The conductive profile T = 1/2 - x, plus (amplitude / 4) sin(pi x)
    times a seeded mix of low modes in y and z, and two seeded rolls of
    speed ``amplitude``, a whole number of pairs across the width:
    vx = A sin(pi x) (cos(k y + phi) + cos(k z + psi)) (0 on the top
    plate's faces, which the mask holds), vy = -A (pi / k) cos(pi x)
    sin(k y + phi), and vz likewise in z.
    With k = pi (an aspect ratio of 2, 4, 6, ...) the rolls are
    divergence-free on the grid too.  Fields are sampled at their own
    positions (T at centres, each velocity on its face)."""
    c = solver.config
    h = c.h
    nx, ny, nz = c.shape
    rng = np.random.default_rng(seed)
    phi, psi = rng.uniform(0.0, 2 * math.pi, size=2)
    weights = rng.uniform(-1.0, 1.0, size=len(_RB_MODES))
    weights /= np.abs(weights).sum()
    thetas = rng.uniform(0.0, 2 * math.pi, size=len(_RB_MODES))
    k = 2 * math.pi * max(round(ny * h / 2), 1) / (ny * h)
    k0y, k0z = 2 * math.pi / (ny * h), 2 * math.pi / (nz * h)
    x, y, z = solver.driver.coords()
    mix = sum(w * jnp.cos(a * k0y * y + b * k0z * z + t)
              for w, (a, b), t in zip(weights, _RB_MODES, thetas))
    a = amplitude
    T = 0.5 - x + 0.25 * a * jnp.sin(math.pi * x) * mix
    vx = a * jnp.sin(math.pi * (x + 0.5 * h)) * (jnp.cos(k * y + phi)
                                                  + jnp.cos(k * z + psi))
    vy = -a * (math.pi / k) * jnp.cos(math.pi * x) * jnp.sin(
        k * (y + 0.5 * h) + phi)
    vz = -a * (math.pi / k) * jnp.cos(math.pi * x) * jnp.sin(
        k * (z + 0.5 * h) + psi)
    fields = dict(vx=vx * state["mask_vx"], vy=vy, vz=vz, T=T)
    return dict(state, **{f: v.astype(jnp.float32)
                          for f, v in fields.items()})


def _rb_nusselt(solver, state, ctx):
    """The conductive heat flux -dT/dx through each plate, its mean over
    the plate, in units of the conductive state's (1 there): the
    one-sided difference between the plate's temperature and the
    adjacent row of cells, half a cell away."""
    import jax

    h = solver.config.h
    T = state["T"]
    hot, cold = PLATE_T
    bottom = jnp.mean(hot - T[0]) / (0.5 * h)
    top = jnp.mean(T[-1] - cold) / (0.5 * h)
    bottom, top = (float(v) for v in jax.device_get((bottom, top)))
    return {"bottom": bottom, "top": top, "mean": 0.5 * (bottom + top)}


@register_scenario
def rayleigh_benard() -> Scenario:
    return Scenario(
        name="rayleigh_benard",
        description="Boussinesq convection between rigid isothermal "
                    "plates heated from below (x vertical), y and z "
                    "periodic: a temperature field with buoyancy",
        builder=_rb_builder,
        params={"ra": ParamSpec(1e6, "Rayleigh number"),
                "pr": ParamSpec(0.7, "Prandtl number")},
        ic_params={"seed": ParamSpec(0, "seed of the perturbation's "
                                        "phases and mode weights"),
                   "amplitude": ParamSpec(0.2, "roll speed; T's "
                                               "perturbation is a quarter")},
        init_fields=_rb_init,
        analyses={"nusselt": _rb_nusselt,
                  "kinetic_energy": _kinetic_energy},
    )
