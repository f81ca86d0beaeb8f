"""Communication-avoiding fused Jacobi smoother (beyond-paper optimization).

The paper's CFD code spends most of its time in the pressure Poisson solve,
and its scalability section identifies boundary exchange as the cost to
minimize.  A TPU-native improvement over exchanging every sweep: widen the
ghost region to ``k`` cells and fuse ``k`` Jacobi sweeps into one kernel
launch — each sweep consumes one ghost ring, so one halo exchange (width k)
feeds k sweeps.  This divides the collective *count* (latency) by k and cuts
exchanged bytes for k>2, at the cost of O(k·ring) redundant flops — the
classic communication-avoiding smoother trade, which favors TPU's
compute-rich/ICI-bound balance.

Both a Pallas 3DBLOCK version (VMEM-resident tile across all k sweeps — the
intermediate sweeps never touch HBM) and a shape-polymorphic jnp version
(oracle + boundary-shell path) are provided.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.generator import KernelContext, element_block_spec
from repro.kernels import stencil3d


def _sweep(p, rhs, h2, omega):
    """One weighted-Jacobi sweep; p padded by 1 relative to output, rhs
    padded to match p (its outer ring is unused)."""
    nbr = (p[2:, 1:-1, 1:-1] + p[:-2, 1:-1, 1:-1]
           + p[1:-1, 2:, 1:-1] + p[1:-1, :-2, 1:-1]
           + p[1:-1, 1:-1, 2:] + p[1:-1, 1:-1, :-2])
    jac = (nbr - h2 * rhs[1:-1, 1:-1, 1:-1]) / 6.0
    return (1.0 - omega) * p[1:-1, 1:-1, 1:-1] + omega * jac


def jacobi_fused_ref(p, rhs, *, h, omega=1.0, sweeps=1):
    """jnp oracle: k fused sweeps; p and rhs padded by ``sweeps`` cells."""
    h2 = h * h
    for _ in range(sweeps):
        p = _sweep(p, rhs, h2, omega)
        rhs = rhs[1:-1, 1:-1, 1:-1]
    return p


def _fused_body(p_ref, rhs_ref, o_ref, *, h2, omega, sweeps):
    p = p_ref[...].astype(jnp.float32)
    rhs = rhs_ref[...].astype(jnp.float32)
    for _ in range(sweeps):
        p = _sweep(p, rhs, h2, omega)
        rhs = rhs[1:-1, 1:-1, 1:-1]
    o_ref[...] = p.astype(o_ref.dtype)


def jacobi_fused(
    p: jnp.ndarray,
    rhs: jnp.ndarray,
    *,
    h: float,
    omega: float = 1.0,
    sweeps: int = 1,
    tile: tuple[int, int, int] = (8, 8, 8),
    interpret: bool = False,
) -> jnp.ndarray:
    """Pallas: k sweeps, one launch; inputs padded by ``sweeps`` per axis."""
    k = sweeps
    interior = tuple(s - 2 * k for s in p.shape)
    tx, ty, tz = (min(t, n) for t, n in zip(tile, interior))
    if any(n % t for n, t in zip(interior, (tx, ty, tz))):
        raise ValueError(f"interior {interior} not divisible by tile {(tx, ty, tz)}")
    grid = (interior[0] // tx, interior[1] // ty, interior[2] // tz)
    halo_spec = element_block_spec(
        (tx + 2 * k, ty + 2 * k, tz + 2 * k),
        lambda i, j, l: (i * tx, j * ty, l * tz),
    )
    out_spec = pl.BlockSpec((tx, ty, tz), lambda i, j, l: (i, j, l))
    body = functools.partial(_fused_body, h2=h * h, omega=omega, sweeps=k)
    return pl.pallas_call(
        body,
        grid=grid,
        in_specs=[halo_spec, halo_spec],
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct(interior, p.dtype),
        interpret=interpret,
    )(p, rhs)


class _Shifted:
    """A kernel body's view of an unpadded field: ``c`` is the field, and
    ``at`` serves the unit offsets from its precomputed face neighbours."""

    def __init__(self, c, neighbours=()):
        self.c = c
        self._at = {(0, 0, 0): c}
        for axis, pair in enumerate(neighbours):
            for step, arr in zip((-1, 1), pair):
                off = [0, 0, 0]
                off[axis] = step
                self._at[tuple(off)] = arr

    def at(self, dx: int = 0, dy: int = 0, dz: int = 0):
        return self._at[(dx, dy, dz)]


def jacobi_pressure_unpadded(p, neighbours, rhs, *, h, omega=1.0):
    """One JACOBI_PRESSURE sweep over unpadded ``p``, reading its six face
    neighbours from ``neighbours`` (``[(minus, plus)]`` per axis, each at
    ``p``'s shape, as ``core.halo.shifted_neighbours`` gives them).  It
    runs the descriptor's own body, so the arithmetic and its order are
    those of the padded sweep, and the result is bitwise the same."""
    ctx = KernelContext({"p": _Shifted(p, neighbours), "rhs": _Shifted(rhs)},
                        {"h": h, "omega": omega})
    return stencil3d.jacobi_pressure_body(ctx)["p"]


# -- the streamed sweep -------------------------------------------------------
# One chunk of a plane is one (8, 128)-tile row high and the plane's full
# z extent wide: z's neighbours are a lane roll inside it, y's a sublane
# roll that takes its end rows from the tiles above and below.
_ROWS = 8
# Bytes of p one grid step sweeps: enough planes that the step's fixed
# cost is small beside its DMAs.
_BLOCK_BYTES = 4 << 20
# A p of at most this many bytes fits the on-chip memory space that XLA
# stages the sweeps' loop carry in (all 64 MiB of p at 256^3 on a v5e), so
# XLA's own sweep already reads it from HBM about once: only a larger p
# is streamed.
_STAGED_BYTES = 64 << 20


def streams(local_shape, specs) -> bool:
    """Whether the step sweeps a local float32 ``p`` of ``local_shape``,
    whose ghosts ``specs`` describe, with :func:`jacobi_pressure_streamed`:
    p is larger than ``_STAGED_BYTES``, its (y, z) planes are whole
    (8, 128) tiles, and y and z are periodic on no mesh axis (x may be
    periodic, or split, or walled: its outer planes are faces)."""
    nx, ny, nz = local_shape[-3:]
    return (nx * ny * nz * 4 > _STAGED_BYTES
            and ny % _ROWS == 0 and nz % 128 == 0
            and all(s.periodic and s.mesh_axis is None for s in specs[1:]))


def streamed_block_planes(nx: int, plane_bytes: int) -> int:
    """Planes of p one grid step of the streamed sweep writes: the fewest
    that divide ``nx`` and hold ``_BLOCK_BYTES`` (2 of 768 x 768 f32, 16 of
    256 x 256)."""
    return next(b for b in range(1, nx + 1)
                if nx % b == 0 and (b * plane_bytes >= _BLOCK_BYTES
                                    or b == nx))


def _streamed_body(p_hbm, *refs, nx, bx, h2, omega, faces):
    """Grid step ``i`` writes planes ``[i*bx, (i+1)*bx)`` of p'.

    p's planes ``-1 .. nx`` (the two outer ones are the x faces) pass
    through a ring of ``2*bx + 2`` VMEM slots, plane ``j`` in slot
    ``(j + 1) % slots``: a step reads its ``bx + 2`` planes there while
    the DMAs of the next step's ``bx`` new planes run, so each plane
    leaves HBM once.  p' is written over p (aliased); no plane is read
    after its block was written back but plane 0 for a periodic x's far
    face, which step 0 keeps in ``wrap``."""
    if faces:
        lo_hbm, hi_hbm, rhs_ref, o_ref, ring, sem = refs
        lo_src, hi_src = lo_hbm.at[0], hi_hbm.at[0]
    else:
        rhs_ref, o_ref, ring, wrap, sem = refs
        lo_src, hi_src = p_hbm.at[nx - 1], wrap
    slots, ny, nz = ring.shape
    i, steps = pl.program_id(0), pl.num_programs(0)

    def copy(src, j):
        s = (j + 1) % slots
        return pltpu.make_async_copy(src, ring.at[s], sem.at[s])

    def start(j):
        @pl.when((j >= 0) & (j < nx))
        def _():
            copy(p_hbm.at[j], j).start()

        @pl.when(j < 0)
        def _():
            copy(lo_src, j).start()

        @pl.when(j >= nx)
        def _():
            copy(hi_src, j).start()

    def wait(j):
        copy(p_hbm.at[0], j).wait()

    @pl.when(i == 0)
    def _():
        if not faces:
            keep = pltpu.make_async_copy(p_hbm.at[0], wrap, sem.at[slots])
            keep.start()
            keep.wait()
        for q in range(bx + 2):
            start(q - 1)

    @pl.when(i + 1 < steps)
    def _():
        for q in range(bx):
            start((i + 1) * bx + 1 + q)

    @pl.when(i == 0)
    def _():
        for q in range(bx + 2):
            wait(q - 1)

    @pl.when(i > 0)
    def _():
        for q in range(bx):
            wait(i * bx + 1 + q)

    row = lax.broadcasted_iota(jnp.int32, (_ROWS, nz), 0)

    def plane(q, carry):
        j = i * bx + q
        below, here, above = j % slots, (j + 1) % slots, (j + 2) % slots

        def chunk(t, carry):
            y = pl.multiple_of(t * _ROWS, _ROWS)
            rows = pl.ds(y, _ROWS)
            c = ring[here, rows, :]
            prev = ring[here, pl.ds(pl.multiple_of((y + ny - _ROWS) % ny,
                                                   _ROWS), _ROWS), :]
            nxt = ring[here, pl.ds(pl.multiple_of((y + _ROWS) % ny, _ROWS),
                                   _ROWS), :]
            ym = jnp.where(row == 0, pltpu.roll(prev, 1, 0),
                           pltpu.roll(c, 1, 0))
            yp = jnp.where(row == _ROWS - 1, pltpu.roll(nxt, _ROWS - 1, 0),
                           pltpu.roll(c, _ROWS - 1, 0))
            # the descriptor's order: x+, x-, y+, y-, z+, z-
            nbr = (ring[above, rows, :] + ring[below, rows, :] + yp + ym
                   + pltpu.roll(c, nz - 1, 1) + pltpu.roll(c, 1, 1))
            jac = (nbr - h2 * rhs_ref[q, rows, :]) / 6.0
            o_ref[q, rows, :] = (1.0 - omega) * c + omega * jac
            return carry

        return lax.fori_loop(0, ny // _ROWS, chunk, carry)

    lax.fori_loop(0, bx, plane, 0)


def jacobi_pressure_streamed(p, rhs, x_faces=None, *, h, omega=1.0,
                             block_planes=None, interpret=False):
    """One JACOBI_PRESSURE sweep over unpadded ``p`` (nx, ny, nz), with
    p's x-planes streamed through VMEM: the jnp template's Jacobi stage
    lowered for the TPU, with the descriptor's arithmetic in its order
    (``stencil3d.jacobi_pressure_body``: the six neighbours summed x+,
    x-, y+, y-, z+, z-, then ``(nbr - h*h*rhs) / 6``, then
    ``(1 - omega) p + omega jac``, in float32).

    A grid step writes ``block_planes`` planes (:func:`streamed_block_planes`
    by default) and reads p's planes from HBM once a sweep, through a ring
    of ``2 * block_planes + 2`` planes in VMEM; rhs and p' move in blocks
    of ``block_planes`` planes, two buffers each.  So a sweep moves three
    field passes (p, rhs, p') and one plane more, and its VMEM is
    ``6 * block_planes + 2`` planes, one more on a periodic x: 31.5 MiB
    at 768 x 768 on a split x, 24.75 MiB at 256 x 256.  y and z are
    periodic: their neighbours are rolls inside a plane.  ``x_faces`` is ``(lo, hi)``, each
    ``(1, ny, nz)``, the ghost planes past x's ends (``halo._ghosts`` on a
    split x); ``None`` means x is periodic and whole, and the kernel reads
    p's far planes itself.  p' overwrites p's buffer.  Results agree with
    :func:`jacobi_pressure_unpadded` to float32 rounding, not bitwise:
    Mosaic and XLA may contract the sums differently."""
    nx, ny, nz = p.shape
    if p.dtype != jnp.float32 or rhs.shape != p.shape:
        raise ValueError(f"p {p.shape} {p.dtype} and rhs {rhs.shape} must "
                         "be float32 fields of one shape")
    if ny % _ROWS or nz % 128:
        raise ValueError(f"planes {(ny, nz)} are not whole (8, 128) tiles")
    plane_bytes = ny * nz * p.dtype.itemsize
    bx = block_planes or streamed_block_planes(nx, plane_bytes)
    if nx % bx:
        raise ValueError(f"{bx} planes a block do not divide nx = {nx}")
    slots = 2 * bx + 2
    faces = x_faces is not None
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    block = pl.BlockSpec((bx, ny, nz), lambda i: (i, 0, 0))
    scratch = [pltpu.VMEM((slots, ny, nz), p.dtype)]
    if not faces:
        scratch.append(pltpu.VMEM((ny, nz), p.dtype))
    scratch.append(pltpu.SemaphoreType.DMA((slots + 1,)))
    vmem = (slots + (0 if faces else 1) + 4 * bx) * plane_bytes
    body = functools.partial(_streamed_body, nx=nx, bx=bx, h2=h * h,
                             omega=omega, faces=faces)
    cells = p.size
    return pl.pallas_call(
        body,
        grid=(nx // bx,),
        in_specs=[hbm] + [hbm, hbm] * faces + [block],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct(p.shape, p.dtype),
        scratch_shapes=scratch,
        input_output_aliases={0: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=vmem + (4 << 20)),
        cost_estimate=pl.CostEstimate(flops=10 * cells, transcendentals=0,
                                      bytes_accessed=3 * 4 * cells),
        interpret=interpret,
    )(p, *(x_faces or ()), rhs)
