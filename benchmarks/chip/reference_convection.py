"""Plain reference of the Rayleigh-Benard step that cell ``rb-g6-dns`` runs.

Boussinesq convection in free-fall units (layer depth 1, plate temperature
difference 1), x vertical with gravity along -x, y and z periodic:

    du/dt + div(u u) = -grad p + nu lap u + T e_x,    div u = 0,
    dT/dt + div(u T) = kappa lap T,

nu = sqrt(Pr / Ra), kappa = 1 / sqrt(Ra Pr).  The plate at x = 0 is hot
(T = +1/2), the one at x = 1 cold (T = -1/2); both are no-slip and
impenetrable.

One step, written from these equations on the staggered MAC grid of
``reference.py`` and importing nothing of the program: T at cell centres
advanced with the state at the step's start (explicit Euler, the flux
through a face its velocity times the mean of the two cells that share
it, 7-point diffusion; T's ghost across a plate is 2 T_plate - the
adjacent row); the velocities by ``reference.advect_diffuse``, vx on the
face between cells i and i+1 taking (T_i + T_{i+1}) / 2 as its forcing,
the top plate's faces vx[N-1] held at 0; then the divergence over dt, a
fixed number of Jacobi sweeps warm-started from the previous p (zero
normal gradient at the plates), its mean removed, and the projection.

``dtype`` is the precision the whole step runs in: float32 is the
reference, bfloat16 the control the check has to reject.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from reference import _at, advect_diffuse, pad

FIELDS = ("vx", "vy", "vz", "p", "T")
T_HOT, T_COLD = 0.5, -0.5
_PERIODIC = ("periodic", "periodic", 0.0, 0.0)
# per field, per axis: (rule_lo, rule_hi, wall_lo, wall_hi), as reference.py
RULES = {
    "vx": (("zero", "zero", 0.0, 0.0), _PERIODIC, _PERIODIC),
    "vy": (("wall", "wall", 0.0, 0.0), _PERIODIC, _PERIODIC),
    "vz": (("wall", "wall", 0.0, 0.0), _PERIODIC, _PERIODIC),
    "p": (("copy", "copy", 0.0, 0.0), _PERIODIC, _PERIODIC),
    "T": (("wall", "wall", T_HOT, T_COLD), _PERIODIC, _PERIODIC),
}
# low modes (in y, in z) of the seeded temperature perturbation
MODES = ((1, 0), (0, 1), (1, 1), (2, 1), (1, 2))


def viscosities(ra: float, pr: float) -> tuple[float, float]:
    """(nu, kappa) of the free-fall units."""
    return math.sqrt(pr / ra), 1.0 / math.sqrt(ra * pr)


def step(state, *, h, dt, nu, kappa, sweeps):
    """One step of ``state`` (vx, vy, vz, p, T) in its own dtype."""
    dtype = state["p"].dtype
    h, dt, nu, kappa = (jnp.asarray(x, dtype) for x in (h, dt, nu, kappa))
    both, low, high = ((1, 1),) * 3, ((1, 0),) * 3, ((0, 1),) * 3
    o = (0, 0, 0)
    vx, vy, vz = (pad(state[f], RULES[f], both) for f in ("vx", "vy", "vz"))
    tq = pad(state["T"], RULES["T"], both)

    # the temperature, with the velocity of the step's start
    ih = 1 / h
    tc = _at(tq, o)
    adv = 0
    for v, off in ((vx, (1, 0, 0)), (vy, (0, 1, 0)), (vz, (0, 0, 1))):
        back = tuple(-d for d in off)
        adv = adv + (_at(v, o) * (0.5 * (tc + _at(tq, off)))
                     - _at(v, back) * (0.5 * (_at(tq, back) + tc))) * ih
    lap = (sum(_at(tq, off) for off in ((1, 0, 0), (-1, 0, 0), (0, 1, 0),
                                        (0, -1, 0), (0, 0, 1), (0, 0, -1)))
           - 6 * tc) * (ih * ih)
    t_new = tc + dt * (kappa * lap - adv)

    # the velocities, with the buoyancy on the x faces
    ux, uy, uz = advect_diffuse(vx, vy, vz, dt, h, nu)
    ux = ux + dt * (0.5 * (tc + _at(tq, (1, 0, 0))))
    top = jnp.arange(ux.shape[0])[:, None, None] == ux.shape[0] - 1
    ux = jnp.where(top, 0, ux).astype(dtype)
    vs = (ux, uy, uz)

    px, py, pz = (pad(v, RULES[f], low) for f, v in zip(("vx", "vy", "vz"),
                                                         vs))
    lo, z = (1, 1, 1), (0, 0, 0)
    div = ((_at(px, z, lo, z) - _at(px, (-1, 0, 0), lo, z))
           + (_at(py, z, lo, z) - _at(py, (0, -1, 0), lo, z))
           + (_at(pz, z, lo, z) - _at(pz, (0, 0, -1), lo, z))) / h
    rhs = div / dt

    def sweep(_, p):
        q = pad(p, RULES["p"], both)
        nbr = (_at(q, (1, 0, 0)) + _at(q, (-1, 0, 0)) + _at(q, (0, 1, 0))
               + _at(q, (0, -1, 0)) + _at(q, (0, 0, 1)) + _at(q, (0, 0, -1)))
        return (nbr - h * h * rhs) / 6

    p = lax.fori_loop(0, sweeps, sweep, state["p"])
    p = p - (jnp.sum(p.astype(jnp.float32)) / math.prod(p.shape)).astype(dtype)
    q = pad(p, RULES["p"], high)
    s = dt / h
    out = [v - s * (_at(q, off, z, (1, 1, 1)) - _at(q, z, z, (1, 1, 1)))
           for v, off in zip(vs, ((1, 0, 0), (0, 1, 0), (0, 0, 1)))]
    out[0] = jnp.where(top, 0, out[0]).astype(dtype)
    return dict(vx=out[0], vy=out[1], vz=out[2], p=p, T=t_new)


def evolve(state, steps: int, **kw):
    """``steps`` steps of ``state`` (see :func:`step`)."""
    return lax.fori_loop(0, steps, lambda _, s: step(s, **kw), state)


def initial_fields(shape, h, seed, amplitude, dtype=jnp.float32):
    """The conductive profile T = 1/2 - x plus (amplitude / 4) sin(pi x)
    times a seeded mix of low modes in y and z, and two rolls of speed
    ``amplitude`` with seeded phases, a whole number of pairs across the
    width (k = pi at an aspect ratio of 6):
    vx = A sin(pi x) (cos(k y + phi) + cos(k z + psi)) (0 on the top
    plate's faces), vy = -A (pi / k) cos(pi x) sin(k y + phi), vz likewise
    in z; each field sampled at its own position, p = 0."""
    nx, ny, nz = shape
    rng = np.random.default_rng(seed)
    phi, psi = rng.uniform(0.0, 2 * math.pi, size=2)
    weights = rng.uniform(-1.0, 1.0, size=len(MODES))
    weights /= np.abs(weights).sum()
    thetas = rng.uniform(0.0, 2 * math.pi, size=len(MODES))
    k = 2 * math.pi * max(round(ny * h / 2), 1) / (ny * h)
    k0y, k0z = 2 * math.pi / (ny * h), 2 * math.pi / (nz * h)
    x = ((jnp.arange(nx) + 0.5) * h)[:, None, None]
    y = ((jnp.arange(ny) + 0.5) * h)[None, :, None]
    zc = ((jnp.arange(nz) + 0.5) * h)[None, None, :]
    mix = sum(w * jnp.cos(a * k0y * y + b * k0z * zc + t)
              for w, (a, b), t in zip(weights, MODES, thetas))
    a = amplitude
    T = 0.5 - x + 0.25 * a * jnp.sin(math.pi * x) * mix
    vx = a * jnp.sin(math.pi * (x + 0.5 * h)) * (jnp.cos(k * y + phi)
                                                  + jnp.cos(k * zc + psi))
    vx = jnp.where(jnp.arange(nx)[:, None, None] == nx - 1, 0.0, vx)
    vy = -a * (math.pi / k) * jnp.cos(math.pi * x) * jnp.sin(
        k * (y + 0.5 * h) + phi)
    vz = -a * (math.pi / k) * jnp.cos(math.pi * x) * jnp.sin(
        k * (zc + 0.5 * h) + psi)
    full = tuple(shape)
    fields = dict(vx=vx, vy=vy, vz=vz, p=jnp.zeros(full), T=T)
    return {f: jnp.broadcast_to(v, full).astype(dtype)
            for f, v in fields.items()}


def temperature_gap(got: dict, want: dict) -> float:
    """max |T - T_ref| over max |T_ref|, of two states on the device."""
    diff, scale = jax.device_get(jax.jit(lambda a, b: (
        jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))),
        jnp.max(jnp.abs(b.astype(jnp.float32)))))(got["T"], want["T"]))
    diff, scale = float(diff), float(scale)
    if not math.isfinite(diff):
        return math.inf
    return diff / scale if scale else (0.0 if diff == 0 else math.inf)
