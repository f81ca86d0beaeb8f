"""3D incompressible Navier-Stokes on a staggered MAC grid — the paper's §4.

Chorin/Hirt-Nichols explicit projection scheme, built entirely from the
framework's descriptor-generated kernels + driver-managed halo exchange:

  1. UPDATE_VELOCITY   u* = u + dt (-adv + nu lap + f)         [stencil kernel]
  2. wall masks        enforce zero wall-normal faces
  3. DIVERGENCE        rhs = div(u*)/dt                        [stencil kernel]
  4. JACOBI_PRESSURE   iterate lap p = rhs                     [stencil kernel]
                       (optionally the fused communication-avoiding smoother)
  5. PROJECT_VELOCITY  u = u* - dt grad p                      [stencil kernel]

Grid convention (see kernels/stencil3d.py): vx[i] at the right x-face of
cell i; the hi wall face is vx[N-1].  Cases: ``cavity`` (lid-driven, lid at
y-hi moving in +x; z periodic so the Ghia 2D profile is recovered) and
``taylor_green`` (triply periodic, analytic solution).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import AxisSpec, Domain, GridDriver, bc_dirichlet, bc_neumann
from repro.core.halo import (exchange_pad, ghost_faces, shifted_neighbours,
                             stencil_step_overlap)
from repro.kernels import ops, ref
from repro.kernels.jacobi import (jacobi_fused_ref, jacobi_pressure_streamed,
                                  jacobi_pressure_unpadded, streams)


def bc_moving_wall(u_wall: float):
    """Tangential-velocity ghost across a wall moving at ``u_wall``:
    ghost = 2 u_wall - mirrored interior (wall value is the face average)."""

    def rule(strip, side):
        return 2.0 * u_wall - jnp.flip(strip, axis=rule.axis)

    return rule


@dataclasses.dataclass(frozen=True)
class CFDConfig:
    shape: tuple[int, int, int] = (64, 64, 4)
    extent: float = 1.0                      # cubic cells: h = extent/shape[0]
    nu: float = 0.01
    dt: float = 2.5e-3
    case: str = "cavity"                     # "cavity" | "taylor_green"
    lid_velocity: float = 1.0
    forcing: tuple[float, float, float] = (0.0, 0.0, 0.0)
    jacobi_iters: int = 40
    jacobi_omega: float = 1.0
    fused_sweeps: int = 1                    # >1: communication-avoiding smoother
    template: str | None = None              # None -> backend default
    interpret: bool = False                  # Pallas interpret mode (CPU 3DBLOCK)
    overlap: bool = True                     # interior/boundary split
    decomposition: tuple = ()                # e.g. ((0,"data"), (1,"model"))

    @property
    def h(self) -> float:
        return self.extent / self.shape[0]

    def cfl(self, umax: float = 1.0) -> float:
        """Stable dt bound: advective + viscous."""
        h = self.h
        return min(0.5 * h / max(umax, 1e-12), h * h / (6.0 * self.nu) * 0.9)


# The per-simulation runtime parameters: everything that may vary between
# ensemble members sharing one compiled step.  Grid geometry (shape, h) and
# solver structure (iterations, overlap, template) stay static — they select
# the compiled executable; these select the physics, as traced f32 scalars.
PARAM_KEYS = ("nu", "dt", "lid_velocity", "fx", "fy", "fz")


def params_from_config(c: CFDConfig) -> dict:
    """The per-simulation scalar struct for ``c`` (f32, like the fields).

    Both the single-run path (``make_step``) and the simulation farm thread
    these through the step, so a farm slot is bit-identical to a serial run
    of the same configuration.
    """
    return {k: jnp.float32(v) for k, v in host_params(c).items()}


def host_params(c: CFDConfig) -> dict:
    """:func:`params_from_config`'s values as host ``np.float32`` scalars:
    what the farm installs at admission, with no device round trip."""
    fx, fy, fz = c.forcing
    vals = dict(nu=c.nu, dt=c.dt, lid_velocity=c.lid_velocity,
                fx=fx, fy=fy, fz=fz)
    return {k: np.float32(vals[k]) for k in PARAM_KEYS}


# Cases whose domain is fully periodic (no wall BCs, no wall masks).
# "kelvin_helmholtz" shares the solver structure of "taylor_green" — its
# shear-layer initial condition is owned by the scenario registry
# (repro.sim.scenarios), not by the solver.
PERIODIC_CASES = ("taylor_green", "kelvin_helmholtz")

# Physics columns of one in-situ health frame, in the order
# ``health_diagnostics`` stacks them.  ``obs.health.DIAG_COLUMNS`` is
# ``("step", *HEALTH_DIAGS)`` — duplicated (not imported) so the solver
# owes nothing to the obs package; a test pins the two tuples.
HEALTH_DIAGS = ("div_linf", "ke", "umax", "cfl", "finite")


class NavierStokes3D:
    """The CFD application object: owns the driver, BCs, and the step."""

    FIELDS = ("vx", "vy", "vz", "p")

    def __init__(self, config: CFDConfig, mesh: jax.sharding.Mesh | None = None):
        self.config = config
        periodic = config.case in PERIODIC_CASES
        self.domain = Domain(
            shape=config.shape,
            spacing=(config.h,) * 3,
            decomposition=dict(config.decomposition),
            periodic=(periodic, periodic, True),
        )
        self.driver = GridDriver(self.domain, mesh)
        self._health_jit = None   # lazy fused health_report executable
        self._build_bcs()

    @property
    def field_pspec(self):
        """PartitionSpec of one field under this solver's decomposition.

        The serial path shards state as ``field_pspec``; the simulation
        farm stacks a slot axis in front and shards as
        ``P(slot_axis, *field_pspec)`` (``sim.ensemble.slot_field_spec``)
        — same grid placement, one more batch dimension.
        """
        return self.domain.pspec()

    # ------------------------------------------------------------------ BCs
    def _bcs_for(self, lid_velocity) -> dict:
        """BC rule table; ``lid_velocity`` may be a traced per-slot scalar."""
        c = self.config
        if c.case in PERIODIC_CASES:
            # fully periodic: no BC rules needed anywhere
            return {f: ((None,) * 3, (None,) * 3) for f in self.FIELDS}
        noslip = bc_moving_wall(0.0)
        lid = bc_moving_wall(lid_velocity)
        zero = bc_dirichlet(0.0)
        neum = bc_neumann()
        # (bc_lo per axis, bc_hi per axis); z is periodic via Domain.periodic
        return {
            # vx: normal to x walls (ghost faces 0), tangential in y (lid at hi)
            "vx": ((zero, noslip, None), (zero, lid, None)),
            # vy: tangential in x, normal to y walls
            "vy": ((noslip, zero, None), (noslip, zero, None)),
            # vz: tangential to x and y walls
            "vz": ((noslip, noslip, None), (noslip, noslip, None)),
            # p: homogeneous Neumann at all walls
            "p": ((neum, neum, None), (neum, neum, None)),
        }

    def _build_bcs(self):
        self.bc = self._bcs_for(self.config.lid_velocity)

    def _specs(self, field: str, bc: dict | None = None
               ) -> tuple[AxisSpec, AxisSpec, AxisSpec]:
        bc_lo, bc_hi = (bc or self.bc)[field]
        return self.driver.axis_specs(bc_lo=bc_lo, bc_hi=bc_hi)

    # --------------------------------------------------------------- fields
    def init_state(self) -> dict:
        c = self.config
        state = self.driver.allocate(self.FIELDS, 0.0)
        state["mask_vx"], state["mask_vy"], state["mask_vz"] = self._masks()
        if c.case == "taylor_green":
            x, y, z = self.driver.coords()
            h = c.h
            # face-centered sample positions (vx at x+(h/2), vy at y+(h/2))
            state["vx"] = jnp.sin(x + 0.5 * h) * jnp.cos(y)
            state["vy"] = -jnp.cos(x) * jnp.sin(y + 0.5 * h)
        return state

    def _masks(self):
        """Zero the wall-normal boundary faces (vx[N-1] on x, etc.)."""
        c = self.config
        sh = self.driver.sharding()
        ones = np.ones(c.shape, np.float32)
        mx, my, mz = ones.copy(), ones.copy(), ones.copy()
        if c.case not in PERIODIC_CASES:
            mx[-1, :, :] = 0.0
            my[:, -1, :] = 0.0
            # z periodic: no vz mask
        # host -> shards directly: staging through jnp.asarray would put
        # the whole grid on the default device first
        if sh is not None:
            return [jax.device_put(m, sh) for m in (mx, my, mz)]
        return [jnp.asarray(m) for m in (mx, my, mz)]

    # ----------------------------------------------------------------- step
    def _global_mean(self, x):
        # sequential per-axis sums, innermost first: the reduction order is
        # then identical with and without a leading slot axis (vmap), which
        # keeps farm slots bit-identical to serial runs
        m = x
        for _ in range(3):
            m = m.sum(axis=-1)
        m = m / np.prod(np.asarray(x.shape[-3:], np.float32))
        axes = tuple(self.domain.decomposition.values())
        if axes:
            m = lax.pmean(m, axes)
        return m

    def _step_local(self, state: dict, params: dict | None = None) -> dict:
        """One dt, operating on local blocks (runs inside shard_map).

        ``params`` is the per-simulation scalar struct (see ``PARAM_KEYS``);
        the farm vmaps this function over a slot axis with batched params,
        the single-run path passes ``params_from_config`` constants.

        Nothing here assumes the local block is the whole grid: ghost
        zones come from ``exchange_pad`` driven by the domain's AxisSpecs,
        so the same trace runs undecomposed (pure BC padding), decomposed
        under ``shard_map`` (ppermute per face), and decomposed *under
        vmap* on a slots × shards farm mesh — the collectives batch over
        the unnamed slot axis, keeping every slot bitwise equal to its
        serial decomposed run.

        Each stage runs in a ``jax.named_scope`` (``update_velocity``,
        ``divergence``, ``jacobi``, ``project``; the ghost fills are
        ``exchange_pad``), so every op of the compiled step carries its
        stage in its metadata, serial, shard-mapped or vmapped: a device
        profile splits the step's time by stage.
        """
        c = self.config
        if params is None:
            params = params_from_config(c)
        kw = dict(template=c.template or "JNP", interpret=c.interpret)
        if kw["template"] == "3DBLOCK":
            # chip-aware roofline tile, resolved per local interior and
            # memoized (autotune.tile_for) — serial and farm runs of the
            # same grid resolve the same tile, a bitwise-parity invariant
            kw["tile"] = "auto"
        h = c.h
        dt, nu = params["dt"], params["nu"]
        bc = self._bcs_for(params["lid_velocity"])
        specs = functools.partial(self._specs, bc=bc)
        vx, vy, vz, p = state["vx"], state["vy"], state["vz"], state["p"]
        mvx, mvy, mvz = state["mask_vx"], state["mask_vy"], state["mask_vz"]

        # -- 1. advection-diffusion (with comm/compute overlap if enabled)
        vel_params = dict(dt=dt, h=h, nu=nu, fx=params["fx"],
                          fy=params["fy"], fz=params["fz"])

        def upd_packed(padded):
            out = ops.update_velocity(padded[0], padded[1], padded[2],
                                      **vel_params, **kw)
            return jnp.stack(out)

        with jax.named_scope("update_velocity"):
            if c.overlap:
                # pack the components on a leading axis; the deep interior
                # runs without any ghost dependency (overlaps the
                # ppermutes), shells are computed from the exchanged pack.
                def pad_packed(pack):
                    return jnp.stack([
                        exchange_pad(pack[i], (1, 1, 1), specs(f))
                        for i, f in enumerate(("vx", "vy", "vz"))
                    ])

                packed = jnp.stack([vx, vy, vz])
                out = stencil_step_overlap(
                    packed, (0, 1, 1, 1), specs=None, kernel=upd_packed,
                    pad_fn=pad_packed)
                vx_s, vy_s, vz_s = out[0], out[1], out[2]
            else:
                pads = [exchange_pad(v, (1, 1, 1), specs(f))
                        for f, v in (("vx", vx), ("vy", vy), ("vz", vz))]
                vx_s, vy_s, vz_s = ops.update_velocity(*pads, **vel_params,
                                                       **kw)
            vx_s, vy_s, vz_s = vx_s * mvx, vy_s * mvy, vz_s * mvz

        # -- 2. divergence rhs
        with jax.named_scope("divergence"):
            pads = [exchange_pad(v, ((1, 0),) * 3, specs(f))
                    for f, v in (("vx", vx_s), ("vy", vy_s), ("vz", vz_s))]
            rhs = ops.divergence(*pads, h=h, **kw) / dt

        # -- 3. pressure Poisson (warm start from previous p)
        p_specs = specs("p")
        k = c.fused_sweeps
        streamed = (k <= 1 and kw["template"] == "JNP"
                    and streams(p.shape, p_specs))

        def unpadded(pcur):
            # the seven-point sweep reads no corners: p's six face
            # neighbours at its own shape, no padded copy of p
            return jacobi_pressure_unpadded(
                pcur, shifted_neighbours(pcur, p_specs), rhs, h=h,
                omega=c.jacobi_omega)

        def on_tpu(pcur):
            # x's outer planes: p's own on a whole periodic x, else the
            # ghost faces (a ppermute on a split x)
            x = p_specs[0]
            faces = (None if x.periodic and x.mesh_axis is None
                     else ghost_faces(pcur, x))
            return jacobi_pressure_streamed(pcur, rhs, faces, h=h,
                                            omega=c.jacobi_omega,
                                            interpret=c.interpret)

        def jacobi_body(_, pcur):
            if streamed:
                # a p larger than XLA stages on chip, in lane-aligned
                # periodic planes: streamed through VMEM on a TPU
                return lax.platform_dependent(pcur, tpu=on_tpu,
                                              default=unpadded)
            if k <= 1 and kw["template"] == "JNP":
                return unpadded(pcur)
            if k <= 1:
                pp = exchange_pad(pcur, (1, 1, 1), p_specs)
                return ops.jacobi_pressure(pp, rhs, h=h, omega=c.jacobi_omega, **kw)
            pp = exchange_pad(pcur, (k, k, k), p_specs)
            rr = exchange_pad(rhs, (k, k, k), p_specs)
            return jacobi_fused_ref(pp, rr, h=h, omega=c.jacobi_omega, sweeps=k)

        iters = max(c.jacobi_iters // max(k, 1), 1)
        with jax.named_scope("jacobi"):
            p_new = lax.fori_loop(0, iters, jacobi_body, p)
            # pin the Neumann null space
            p_new = p_new - self._global_mean(p_new)

        # -- 4. projection
        with jax.named_scope("project"):
            pp = exchange_pad(p_new, ((0, 1),) * 3, p_specs)
            vx_n, vy_n, vz_n = ops.project_velocity(vx_s, vy_s, vz_s, pp,
                                                    dt=dt, h=h, **kw)
            vx_n, vy_n, vz_n = vx_n * mvx, vy_n * mvy, vz_n * mvz

        return dict(state, vx=vx_n, vy=vy_n, vz=vz_n, p=p_new)

    def make_step(self) -> Callable[[dict], dict]:
        """Jitted global step (shard_map'd when a mesh decomposes the grid).

        The config's scalars are threaded as f32 traced values through the
        same parameterized step the simulation farm vmaps — on the 3DBLOCK
        (Pallas) template they ride the generator's scalar-table operand
        (scalar prefetch on real TPU) exactly like a farm slot's table row —
        so a serial run is the bitwise reference for a farm slot with the
        same parameters on every template.
        """
        c = self.config
        # the state's tree structure only: no second set of fields
        example = jax.eval_shape(self.init_state)
        params = params_from_config(c)
        jstep = self.driver.sharded_step_tree(self._step_local, example, params)
        return lambda s: jstep(s, params)

    # ------------------------------------------------------------ analysis
    def divergence_of(self, state: dict) -> jnp.ndarray:
        def local(vx, vy, vz):
            pads = [exchange_pad(v, ((1, 0),) * 3, self._specs(f))
                    for f, v in (("vx", vx), ("vy", vy), ("vz", vz))]
            return ops.divergence(*pads, h=self.config.h, template="JNP")

        if self.driver.mesh is None:
            return local(state["vx"], state["vy"], state["vz"])
        spec = self.domain.pspec()
        f = jax.shard_map(local, mesh=self.driver.mesh,
                          in_specs=(spec, spec, spec), out_specs=spec,
                          check_vma=False)
        return f(state["vx"], state["vy"], state["vz"])

    def kinetic_energy(self, state: dict) -> float:
        return float(0.5 * sum(jnp.mean(state[f] ** 2)
                               for f in ("vx", "vy", "vz")))

    def health_diagnostics(self, state: dict,
                           params: dict | None = None) -> jnp.ndarray:
        """One fused ``(len(HEALTH_DIAGS),)`` f32 vector of in-situ health
        diagnostics: divergence L∞, kinetic energy, max|u|, CFL number,
        and a finite-fields sentinel (1.0 = no NaN/Inf in any dynamic
        field — the velocities and the pressure).

        Local-block semantics like ``_step_local``: the stencil is
        ghost-free (interior slicing) and reductions finish with
        ``pmax``/``pmin``/``pmean`` over the decomposition axes, so the
        same function runs serially, vmapped over farm slots, and inside
        ``shard_map`` — with zero halo traffic of its own.  Read-only
        (no state writes): compiling it alongside the step cannot
        perturb the step's numerics.
        """
        c = self.config
        if params is None:
            params = params_from_config(c)
        axes = tuple(self.domain.decomposition.values())

        def gmax(x):
            return lax.pmax(x, axes) if axes else x

        def seqmax(x):
            # sequential per-axis maxes: XLA:CPU lowers one multi-axis
            # (or flattened) NaN-propagating max-reduce to a scalar loop,
            # which is ~3x slower than chained single-axis reduces; this
            # runs inside every farm chunk, so the lowering matters
            for _ in range(3):
                x = x.max(axis=-1)
            return x

        # interior one-sided divergence: identical to the ghost-padded
        # stencil on every cell that has real (non-BC) neighbors, but it
        # is pure slicing — no padded field copies, no halo traffic, one
        # fused kernel.  A blow-up is a volume phenomenon; the skipped
        # boundary planes cannot hide one from the L-inf
        vx, vy, vz = state["vx"], state["vy"], state["vz"]
        div = ((vx[1:, 1:, 1:] - vx[:-1, 1:, 1:])
               + (vy[1:, 1:, 1:] - vy[1:, :-1, 1:])
               + (vz[1:, 1:, 1:] - vz[1:, 1:, :-1])) / c.h
        div_linf = gmax(seqmax(jnp.abs(div)))
        # max|u| as ONE volume reduce over the elementwise 3-field max
        # (equal to the max of per-field maxes, at a third of the reduce)
        umax = gmax(seqmax(jnp.maximum(jnp.maximum(jnp.abs(vx),
                                                   jnp.abs(vy)),
                                       jnp.abs(vz))))
        ke2 = vx * vx + vy * vy + vz * vz
        for _ in range(3):      # sequential per-axis sums like _global_mean
            ke2 = ke2.sum(axis=-1)
        ke = 0.5 * ke2 / np.prod(np.asarray(vx.shape[-3:], np.float32))
        if axes:
            ke = lax.pmean(ke, axes)
        cfl = umax * params["dt"] / c.h
        # sentinel without boolean volume reduces: NaN/Inf in any velocity
        # poisons umax or ke (max and sum both propagate non-finites); the
        # pressure — untouched by the three stats above — contributes one
        # cheap mean-of-field sum
        psum = state["p"]
        for _ in range(3):
            psum = psum.sum(axis=-1)
        finite = jnp.isfinite(div_linf + ke + umax + psum)
        finite = finite.astype(jnp.float32)
        if axes:
            finite = lax.pmin(finite, axes)
        return jnp.stack([div_linf, ke, umax, cfl, finite]
                         ).astype(jnp.float32)

    def health_report(self, state: dict) -> dict:
        """Named health diagnostics of ``state`` as plain floats — ONE
        fused dispatch and ONE host fetch, however many numbers come
        back (the lazy replacement for per-diagnostic ``float(...)``
        host syncs in analysis code)."""
        fields = [state[f] for f in self.FIELDS]
        if self._health_jit is None:
            def local(vx, vy, vz, p):
                return self.health_diagnostics(
                    {"vx": vx, "vy": vy, "vz": vz, "p": p})

            if self.driver.mesh is None:
                self._health_jit = jax.jit(local)
            else:
                from jax.sharding import PartitionSpec

                spec = self.domain.pspec()
                self._health_jit = jax.jit(jax.shard_map(
                    local, mesh=self.driver.mesh, in_specs=(spec,) * 4,
                    out_specs=PartitionSpec(), check_vma=False))
        vec = np.asarray(self._health_jit(*fields))
        return {k: float(v) for k, v in zip(HEALTH_DIAGS, vec)}
