"""Plain reference of the projection step that the cells run.

Written from the scheme's equations, apart from the code under test and
importing none of it: a staggered MAC grid (p at cell centres, vx, vy, vz
on the right x, y and z faces of each cell), one explicit step of central
advection and 7-point diffusion, the divergence over dt, a fixed number of
Jacobi sweeps for the pressure warm-started from the previous one, its mean
removed, and the projection.  Ghost cells are filled axis by axis in the
order x, y, z, each axis padding the already padded earlier ones, so the
edge ghosts come out as the boundary rules give them.

Boundary rules (the cavity's lid is the y-hi wall, moving in +x; z is
periodic in both cases):

* ``periodic``: the opposite interior row.
* ``zero``: 0 (the wall-normal face ghost).
* ``wall``: 2 * wall speed - the adjacent interior row (a tangential
  velocity across a wall; the wall value is the face average).
* ``copy``: the adjacent interior row (zero normal gradient of p).

``dtype`` is the precision the whole step runs in: float32 is the
reference, bfloat16 the control the check has to reject.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

FIELDS = ("vx", "vy", "vz", "p")
VELOCITY = ("vx", "vy", "vz")


def _ghost(rule, row, wall):
    if rule == "zero":
        return jnp.zeros_like(row)
    if rule == "wall":
        return (2 * wall - row).astype(row.dtype)
    if rule == "copy":
        return row
    raise ValueError(f"unknown boundary rule {rule!r}")


def pad_axis(u, axis, lo, hi, rule_lo, rule_hi, wall_lo=0.0, wall_hi=0.0,
             ring=None):
    """Pad ``u`` by ``lo`` and ``hi`` (0 or 1) ghost rows along ``axis``.

    ``ring`` names a mesh axis the array is split over along ``axis``
    (periodic only): the ghost rows then come from the neighbouring block.
    """
    n = u.shape[axis]
    first = lax.slice_in_dim(u, 0, 1, axis=axis)
    last = lax.slice_in_dim(u, n - 1, n, axis=axis)
    parts = [u]
    if rule_lo == "periodic":
        g_lo, g_hi = last, first
        if ring is not None:
            k = lax.axis_size(ring)
            g_lo = lax.ppermute(last, ring, [(i, (i + 1) % k) for i in range(k)])
            g_hi = lax.ppermute(first, ring,
                                [(i, (i - 1) % k) for i in range(k)])
    else:
        g_lo, g_hi = _ghost(rule_lo, first, wall_lo), _ghost(rule_hi, last,
                                                              wall_hi)
    if lo:
        parts.insert(0, g_lo)
    if hi:
        parts.append(g_hi)
    return jnp.concatenate(parts, axis=axis) if len(parts) > 1 else u


def boundary_rules(case: str, lid):
    """Per field, per axis: (rule_lo, rule_hi, wall_lo, wall_hi)."""
    per = ("periodic", "periodic", 0.0, 0.0)
    if case == "taylor_green":
        return {f: (per, per, per) for f in FIELDS}
    noslip = ("wall", "wall", 0.0, 0.0)
    normal = ("zero", "zero", 0.0, 0.0)
    copy = ("copy", "copy", 0.0, 0.0)
    return {
        "vx": (normal, ("wall", "wall", 0.0, lid), per),
        "vy": (noslip, normal, per),
        "vz": (noslip, noslip, per),
        "p": (copy, copy, per),
    }


def pad(u, rules, widths, ring=None):
    """Pad all three axes; ``widths`` is ((lo, hi),) * 3."""
    for axis, ((lo, hi), (r_lo, r_hi, w_lo, w_hi)) in enumerate(
            zip(widths, rules)):
        u = pad_axis(u, axis, lo, hi, r_lo, r_hi, w_lo, w_hi,
                     ring=ring if axis == 0 else None)
    return u


def _at(u, off, lo=(1, 1, 1), hi=(1, 1, 1)):
    """Interior view of padded ``u`` shifted by ``off``."""
    return u[tuple(slice(l + o, u.shape[a] - h + o)
                   for a, (l, h, o) in enumerate(zip(lo, hi, off)))]


def advect_diffuse(vx, vy, vz, dt, h, nu):
    """u* = u + dt (-div(u u) + nu lap u) on padded (1, 1) inputs."""
    ih = 1 / h

    def avg(f, o1, o2):
        return 0.5 * (_at(f, o1) + _at(f, o2))

    def lap(f):
        return (_at(f, (1, 0, 0)) + _at(f, (-1, 0, 0)) + _at(f, (0, 1, 0))
                + _at(f, (0, -1, 0)) + _at(f, (0, 0, 1)) + _at(f, (0, 0, -1))
                - 6 * _at(f, (0, 0, 0))) * (ih * ih)

    o = (0, 0, 0)
    # x-momentum on x-faces
    fxx = (avg(vx, o, (1, 0, 0)) ** 2 - avg(vx, (-1, 0, 0), o) ** 2) * ih
    fxy = (avg(vx, o, (0, 1, 0)) * avg(vy, o, (1, 0, 0))
           - avg(vx, (0, -1, 0), o) * avg(vy, (0, -1, 0), (1, -1, 0))) * ih
    fxz = (avg(vx, o, (0, 0, 1)) * avg(vz, o, (1, 0, 0))
           - avg(vx, (0, 0, -1), o) * avg(vz, (0, 0, -1), (1, 0, -1))) * ih
    nx = _at(vx, o) + dt * (-(fxx + fxy + fxz) + nu * lap(vx))
    # y-momentum on y-faces
    fyx = (avg(vy, o, (1, 0, 0)) * avg(vx, o, (0, 1, 0))
           - avg(vy, (-1, 0, 0), o) * avg(vx, (-1, 0, 0), (-1, 1, 0))) * ih
    fyy = (avg(vy, o, (0, 1, 0)) ** 2 - avg(vy, (0, -1, 0), o) ** 2) * ih
    fyz = (avg(vy, o, (0, 0, 1)) * avg(vz, o, (0, 1, 0))
           - avg(vy, (0, 0, -1), o) * avg(vz, (0, 0, -1), (0, 1, -1))) * ih
    ny = _at(vy, o) + dt * (-(fyx + fyy + fyz) + nu * lap(vy))
    # z-momentum on z-faces
    fzx = (avg(vz, o, (1, 0, 0)) * avg(vx, o, (0, 0, 1))
           - avg(vz, (-1, 0, 0), o) * avg(vx, (-1, 0, 0), (-1, 0, 1))) * ih
    fzy = (avg(vz, o, (0, 1, 0)) * avg(vy, o, (0, 0, 1))
           - avg(vz, (0, -1, 0), o) * avg(vy, (0, -1, 0), (0, -1, 1))) * ih
    fzz = (avg(vz, o, (0, 0, 1)) ** 2 - avg(vz, (0, 0, -1), o) ** 2) * ih
    nz = _at(vz, o) + dt * (-(fzx + fzy + fzz) + nu * lap(vz))
    return nx, ny, nz


def step(state, *, case, h, dt, nu, lid, sweeps, masks, ring=None):
    """One projection step of ``state`` (vx, vy, vz, p) in its own dtype.

    ``masks`` zero the wall-normal faces on the walls (cavity) and are
    None for the periodic box.  ``ring``: see :func:`pad_axis`.
    """
    dtype = state["p"].dtype
    dt, nu, lid = (jnp.asarray(x, dtype) for x in (dt, nu, lid))
    h = jnp.asarray(h, dtype)
    rules = boundary_rules(case, lid)
    both, low, high = ((1, 1),) * 3, ((1, 0),) * 3, ((0, 1),) * 3
    vx, vy, vz = (pad(state[f], rules[f], both, ring) for f in VELOCITY)
    vs = advect_diffuse(vx, vy, vz, dt, h, nu)
    if masks is not None:
        vs = tuple(v * m for v, m in zip(vs, masks))
    px, py, pz = (pad(v, rules[f], low, ring) for f, v in zip(VELOCITY, vs))
    lo = (1, 1, 1)
    z = (0, 0, 0)
    div = ((_at(px, z, lo, z) - _at(px, (-1, 0, 0), lo, z))
           + (_at(py, z, lo, z) - _at(py, (0, -1, 0), lo, z))
           + (_at(pz, z, lo, z) - _at(pz, (0, 0, -1), lo, z))) / h
    rhs = div / dt

    def sweep(_, p):
        q = pad(p, rules["p"], both, ring)
        nbr = (_at(q, (1, 0, 0)) + _at(q, (-1, 0, 0)) + _at(q, (0, 1, 0))
               + _at(q, (0, -1, 0)) + _at(q, (0, 0, 1)) + _at(q, (0, 0, -1)))
        return (nbr - h * h * rhs) / 6

    p = lax.fori_loop(0, sweeps, sweep, state["p"])
    total = jnp.sum(p.astype(jnp.float32))
    count = math.prod(p.shape)
    if ring is not None:
        total = lax.psum(total, ring)
        count *= lax.axis_size(ring)
    p = p - (total / count).astype(dtype)
    q = pad(p, rules["p"], high, ring)
    s = dt / h
    out = [v - s * (_at(q, off, z, (1, 1, 1)) - _at(q, z, z, (1, 1, 1)))
           for v, off in zip(vs, ((1, 0, 0), (0, 1, 0), (0, 0, 1)))]
    if masks is not None:
        out = [v * m for v, m in zip(out, masks)]
    return dict(vx=out[0], vy=out[1], vz=out[2], p=p)


def cavity_masks(shape, dtype):
    mx = jnp.ones(shape, dtype).at[-1, :, :].set(0)
    my = jnp.ones(shape, dtype).at[:, -1, :].set(0)
    return mx, my, jnp.ones(shape, dtype)


def taylor_green_fields(shape, extent, phase, dtype=jnp.float32,
                        x_offset=0):
    """The 2D Taylor-Green vortex, shifted by ``phase`` = (x0, y0), sampled
    on the faces; ``x_offset`` is the first x row of a block."""
    n0, n1, n2 = shape
    h = extent / n1
    i = (jnp.arange(n0) + x_offset)[:, None, None]
    j = jnp.arange(n1)[None, :, None]
    x = (i + 0.5) * h - phase[0]
    y = (j + 0.5) * h - phase[1]
    vx = jnp.sin(x + 0.5 * h) * jnp.cos(y)
    vy = -jnp.cos(x) * jnp.sin(y + 0.5 * h)
    full = (n0, n1, n2)
    return {"vx": jnp.broadcast_to(vx, full).astype(dtype),
            "vy": jnp.broadcast_to(vy, full).astype(dtype),
            "vz": jnp.zeros(full, dtype), "p": jnp.zeros(full, dtype)}


def field_gap(got: dict, want: dict) -> float:
    """Widest gap between two states, as a share of the reference's scale:
    velocities against the largest speed of the reference flow, p against
    the reference's largest |p|.  Both are host (numpy) dicts."""
    import numpy as np

    return gap_from_terms({
        f: (np.abs(np.asarray(got[f], np.float64)
                   - np.asarray(want[f], np.float64)).max(),
            np.abs(np.asarray(want[f], np.float64)).max())
        for f in FIELDS})


def evolve(state, steps: int, **kw):
    """``steps`` projection steps of ``state`` (see :func:`step`)."""
    return lax.fori_loop(0, steps, lambda _, s: step(s, **kw), state)


def evolve_members(nu, dt, steps, *, shape, h, lid, sweeps, dtype, last):
    """Cavity members stepped from rest, each ``steps[m]`` steps with its
    own ``nu[m]`` and ``dt[m]``; returns their stacked states.  ``last``
    bounds every member's steps (one compiled program for any mix)."""
    masks = cavity_masks(shape, dtype)

    def one(nu_m, dt_m, n_m):
        s0 = {f: jnp.zeros(shape, dtype) for f in FIELDS}

        def body(i, s):
            new = step(s, case="cavity", h=h, dt=dt_m, nu=nu_m, lid=lid,
                       sweeps=sweeps, masks=masks)
            return {f: jnp.where(i < n_m, new[f], s[f]) for f in FIELDS}

        return lax.fori_loop(0, last, body, s0)

    return jax.jit(jax.vmap(one))(jnp.asarray(nu, jnp.float32),
                                  jnp.asarray(dt, jnp.float32),
                                  jnp.asarray(steps, jnp.int32))


def _gap_terms(got: dict, want: dict):
    """Per field: (max |got - want|, max |want|), computed in float32."""
    return {f: (jnp.max(jnp.abs(got[f].astype(jnp.float32)
                                - want[f].astype(jnp.float32))),
                jnp.max(jnp.abs(want[f].astype(jnp.float32))))
            for f in FIELDS}


def gap_from_terms(terms: dict) -> float:
    """:func:`field_gap` from per-field (max |got - want|, max |want|)."""
    vscale = max(float(terms[f][1]) for f in VELOCITY)
    worst = 0.0
    for f in FIELDS:
        diff, scale = (float(x) for x in terms[f])
        if f in VELOCITY:
            scale = vscale
        if not math.isfinite(diff):
            return math.inf
        worst = max(worst, diff / scale if scale else
                    (0.0 if diff == 0 else math.inf))
    return worst


def device_gap(got: dict, want: dict) -> float:
    """:func:`field_gap` of two states held on the device(s)."""
    return gap_from_terms(jax.device_get(jax.jit(_gap_terms)(got, want)))
