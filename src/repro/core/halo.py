"""Driver-managed ghost-zone (halo) exchange — the paper's §1.1/§2 on TPU.

In Cactus, the driver partitions the grid over MPI ranks and fills each
rank's *ghost region* from its neighbors before stencil kernels run.  On a
TPU mesh the same pattern is a ``jax.lax.ppermute`` (collective-permute —
nearest-neighbor ICI traffic) per face, executed inside ``jax.shard_map``.

Fields are stored globally **unpadded**; the halo is materialized transiently
per kernel application (``exchange_pad``), exactly mirroring the MPI
send/recv into ghost buffers.  Physical boundaries are filled by boundary
condition rules on the edge shards.

Communication/computation overlap (the paper's §1.2 headline optimization) is
provided by :func:`stencil_step_overlap`: the interior update is data-
independent of the exchanged strips, so XLA's latency-hiding scheduler can
run the ``collective-permute`` concurrently with the interior compute — the
TPU analogue of CUDA async copy + concurrent execution.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
from jax import lax

# A BC rule maps (strip, side) -> ghost strip, where ``strip`` is the
# ``width``-wide slab of interior cells adjacent to the physical boundary
# (ordered as stored, i.e. strip[0] is closest to the domain for side "lo"
# ... strip[-1] closest for side "hi").
BCRule = Callable[[jnp.ndarray, str], jnp.ndarray]


def bc_dirichlet(value: float) -> BCRule:
    def rule(strip: jnp.ndarray, side: str) -> jnp.ndarray:
        return jnp.full_like(strip, value)

    return rule


def bc_neumann() -> BCRule:
    """Zero-gradient: mirror the adjacent interior cells."""

    def rule(strip: jnp.ndarray, side: str) -> jnp.ndarray:
        return jnp.flip(strip, axis=rule.axis)  # axis injected by _pad_axis

    return rule


def bc_mirror(sign: float = -1.0) -> BCRule:
    """Reflection BC: ghost = sign * mirrored interior (no-slip walls)."""

    def rule(strip: jnp.ndarray, side: str) -> jnp.ndarray:
        return sign * jnp.flip(strip, axis=rule.axis)

    return rule


@dataclasses.dataclass(frozen=True)
class AxisSpec:
    """How one array axis is decomposed and bounded.

    ``mesh_axis=None`` means the axis is not decomposed (single shard); the
    exchange then degenerates to pure boundary-condition padding, which is
    also the single-device test path.
    """

    array_axis: int
    mesh_axis: str | None = None
    periodic: bool = False
    bc_lo: BCRule | None = None
    bc_hi: BCRule | None = None


def _shift_perm(n: int, shift: int, periodic: bool) -> list[tuple[int, int]]:
    if periodic:
        return [(i, (i + shift) % n) for i in range(n)]
    return [(i, i + shift) for i in range(n) if 0 <= i + shift < n]


def _norm_width(w) -> tuple[int, int]:
    """Width spec: int (symmetric) or (lo, hi) one-sided ghost widths."""
    if isinstance(w, int):
        return (w, w)
    lo, hi = w
    return (int(lo), int(hi))


def _apply_bc(rule: BCRule | None, strip: jnp.ndarray, side: str,
              axis: int) -> jnp.ndarray:
    if rule is None:
        return jnp.zeros_like(strip)
    rule.axis = axis  # let flip-based rules know the axis
    return rule(strip, side)


def _ghosts(u: jnp.ndarray, wlo: int, whi: int, spec: AxisSpec
            ) -> tuple[jnp.ndarray | None, jnp.ndarray | None]:
    """The ``wlo``- and ``whi``-wide ghost slabs of ``u`` along one axis
    (``None`` for a zero width): a periodic wrap on an undecomposed
    periodic axis, the BC rule on a wall, a ``ppermute`` from the
    neighbouring shard on a mesh axis (the BC rule on a non-periodic
    mesh axis's edge shards)."""
    ax = spec.array_axis
    size = u.shape[ax]
    if size < max(wlo, whi):
        raise ValueError(
            f"local extent {size} on axis {ax} smaller than halo width {(wlo, whi)}"
        )

    ghost_lo = ghost_hi = None
    if wlo:
        strip_hi_lo = lax.slice_in_dim(u, size - wlo, size, axis=ax)  # sent right
        my_lo = lax.slice_in_dim(u, 0, wlo, axis=ax)
        if spec.mesh_axis is None:
            ghost_lo = (strip_hi_lo if spec.periodic
                        else _apply_bc(spec.bc_lo, my_lo, "lo", ax))
        else:
            n = lax.axis_size(spec.mesh_axis)
            with jax.named_scope("halo_permute"):
                ghost_lo = lax.ppermute(strip_hi_lo, spec.mesh_axis,
                                        _shift_perm(n, +1, spec.periodic))
            if not spec.periodic:
                idx = lax.axis_index(spec.mesh_axis)
                ghost_lo = jnp.where(
                    idx == 0, _apply_bc(spec.bc_lo, my_lo, "lo", ax), ghost_lo)
    if whi:
        strip_lo_hi = lax.slice_in_dim(u, 0, whi, axis=ax)  # sent left
        my_hi = lax.slice_in_dim(u, size - whi, size, axis=ax)
        if spec.mesh_axis is None:
            ghost_hi = (strip_lo_hi if spec.periodic
                        else _apply_bc(spec.bc_hi, my_hi, "hi", ax))
        else:
            n = lax.axis_size(spec.mesh_axis)
            with jax.named_scope("halo_permute"):
                ghost_hi = lax.ppermute(strip_lo_hi, spec.mesh_axis,
                                        _shift_perm(n, -1, spec.periodic))
            if not spec.periodic:
                idx = lax.axis_index(spec.mesh_axis)
                ghost_hi = jnp.where(
                    idx == n - 1, _apply_bc(spec.bc_hi, my_hi, "hi", ax), ghost_hi)
    return ghost_lo, ghost_hi


def _pad_axis(u: jnp.ndarray, width, spec: AxisSpec) -> jnp.ndarray:
    """Fill ghosts along one axis: neighbor exchange + physical BCs."""
    wlo, whi = _norm_width(width)
    if wlo == 0 and whi == 0:
        return u
    ghost_lo, ghost_hi = _ghosts(u, wlo, whi, spec)
    parts = [g for g in (ghost_lo, u, ghost_hi) if g is not None]
    return jnp.concatenate(parts, axis=spec.array_axis)


def exchange_pad(
    u: jnp.ndarray, widths: Sequence, specs: Sequence[AxisSpec]
) -> jnp.ndarray:
    """Materialize the ghost region: pad ``u`` by ``widths[i]`` along each spec.

    Each width is an int (symmetric) or a ``(lo, hi)`` pair for one-sided
    stencils.  Must run inside ``shard_map`` when any spec names a mesh axis.
    Corner ghosts are produced correctly because later axes exchange the
    already-padded earlier axes (the standard two-phase corner trick).
    Its ops carry the ``exchange_pad`` scope in their metadata, and each
    ``collective-permute`` the nested ``halo_permute`` scope.
    """
    if len(widths) != len(specs):
        raise ValueError("widths and specs length mismatch")
    with jax.named_scope("exchange_pad"):
        for w, spec in zip(widths, specs):
            u = _pad_axis(u, w, spec)
    return u


def ghost_faces(u: jnp.ndarray, spec: AxisSpec
                ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The one-wide ghosts past ``u``'s two ends along ``spec``'s axis,
    as :func:`exchange_pad` fills them (:func:`_ghosts`), under its
    ``exchange_pad`` scope: a kernel that reads ``u``'s interior itself
    takes these as its outer planes."""
    with jax.named_scope("exchange_pad"):
        return _ghosts(u, 1, 1, spec)


def _shift(u: jnp.ndarray, axis: int, k: int) -> jnp.ndarray:
    """``v[i] = u[i - k]`` along ``axis`` where that row exists, else 0."""
    cfg = [(0, 0, 0)] * u.ndim
    cfg[axis] = (k, -k, 0)
    return lax.pad(u, jnp.zeros((), u.dtype), cfg)


def _edge_ghost(rule: BCRule | None, u: jnp.ndarray, side: str,
                axis: int) -> jnp.ndarray:
    """The one-wide ghost that ``rule`` gives, for every row of ``u``.

    A one-wide strip is its own mirror image, so a rule acts on it value
    by value: run on ``u`` seen as a stack of one-wide strips, it gives at
    each edge row exactly the ghost :func:`_ghosts` would, with no strip
    cut out of ``u``."""
    strips = jnp.expand_dims(u, axis)
    return jnp.squeeze(_apply_bc(rule, strips, side, axis), axis)


def shifted_neighbours(
    u: jnp.ndarray, specs: Sequence[AxisSpec]
) -> list[tuple[jnp.ndarray, jnp.ndarray]]:
    """``u``'s two face neighbours along each spec's axis, at ``u``'s shape.

    For axis ``a`` the pair is ``(u_minus, u_plus)`` with
    ``u_minus[i] = u[i-1]`` and ``u_plus[i] = u[i+1]`` along ``a``; the
    row past each end is the one-wide ghost that :func:`exchange_pad`
    would fill.  On an undecomposed axis that ghost is read from ``u``
    itself: its far row on a periodic axis, the BC rule of its edge row
    on a wall (:func:`_edge_ghost`).  On a mesh axis it is the face of
    :func:`_ghosts` (same ``ppermute``, same BC on the edge shards).  A
    stencil that reads no corners (the seven-point Jacobi sweep) needs
    nothing else, so no padded copy of ``u`` and no ghost strip of an
    undecomposed axis is built: the reads fuse into the stencil's single
    pass at the output's shape.  Its ops carry the ``exchange_pad``
    scope, its ``collective-permute``s the nested ``halo_permute`` scope.
    """
    out = []
    with jax.named_scope("exchange_pad"):
        for spec in specs:
            ax = spec.array_axis
            size = u.shape[ax]
            if spec.mesh_axis is not None:
                one_axis = _pad_axis(u, 1, spec)
                out.append((lax.slice_in_dim(one_axis, 0, size, axis=ax),
                            lax.slice_in_dim(one_axis, 2, size + 2, axis=ax)))
                continue
            if spec.periodic:
                lo, hi = _shift(u, ax, 1 - size), _shift(u, ax, size - 1)
            else:
                lo = _edge_ghost(spec.bc_lo, u, "lo", ax)
                hi = _edge_ghost(spec.bc_hi, u, "hi", ax)
            row = lax.broadcasted_iota(jnp.int32, u.shape, ax)
            out.append((jnp.where(row == 0, lo, _shift(u, ax, 1)),
                        jnp.where(row == size - 1, hi, _shift(u, ax, -1))))
    return out


def stencil_step_overlap(
    u: jnp.ndarray,
    widths: Sequence[int],
    specs: Sequence[AxisSpec],
    kernel: Callable[[jnp.ndarray], jnp.ndarray],
    kernel_deep: Callable[[jnp.ndarray], jnp.ndarray] | None = None,
    pad_fn: Callable[[jnp.ndarray], jnp.ndarray] | None = None,
) -> jnp.ndarray:
    """Apply ``kernel`` (padded -> interior) with comm/compute overlap.

    This is the paper's headline optimization (async copy + concurrent
    execution), restructured for XLA: the *deep interior* of the local block
    needs no ghost data, so ``kernel(u)`` — which has no data dependency on
    the ``ppermute`` results — runs concurrently with the exchange under
    XLA's latency-hiding scheduler.  Only thin boundary *shells* (width =
    halo, per face) are computed from the exchanged array afterwards.

    ``kernel`` must be shape-polymorphic (maps an array padded by ``widths``
    to its interior); ``kernel_deep``, if given, is used for the large
    aligned interior block (e.g. the Pallas 3DBLOCK kernel) while ``kernel``
    handles the thin shells (the fused-jnp template).

    Result equals ``kernel(exchange_pad(u, widths, specs))`` (tested); the
    difference is the dataflow graph's schedulability and ~zero recompute.
    """
    if len(widths) != len(u.shape):
        raise ValueError("widths must cover every array axis (use 0 to skip)")
    ws = [_norm_width(w) for w in widths]
    # issue the exchange FIRST; pad_fn lets callers pad packed multi-field
    # arrays with per-field BC rules (must produce ghosts matching `widths`)
    padded = pad_fn(u) if pad_fn is not None else exchange_pad(u, widths, specs)
    deep = (kernel_deep or kernel)(u)  # no ghost dependency -> overlappable

    # Assemble per axis, peeling lo/hi shells computed from the padded array.
    # Output rows [a, b) on an axis with ghosts (lo, hi) need padded rows
    # [a, b + lo + hi).
    def shell(axis: int, side: str, row_lo: list[int], row_hi: list[int]):
        """kernel() over the slab producing the (lo|hi) shell of `axis`."""
        lo, hi = ws[axis]
        sl = []
        for a, ((la, ha), na) in enumerate(zip(ws, u.shape)):
            if a < axis:
                sl.append(slice(row_lo[a], row_hi[a] + la + ha))
            elif a == axis:
                sl.append(slice(0, 2 * lo + hi) if side == "lo"
                          else slice(na - hi, na + lo + hi))
            else:
                sl.append(slice(None))  # full padded extent
        return kernel(padded[tuple(sl)])

    # innermost: deep block; wrap outwards in reverse axis order
    out = deep
    row_lo = [lo for lo, _ in ws]
    row_hi = [n - hi for n, (_, hi) in zip(u.shape, ws)]
    for axis in reversed(range(len(ws))):
        lo, hi = ws[axis]
        if lo == 0 and hi == 0:
            continue
        pieces = []
        if lo:
            pieces.append(shell(axis, "lo", row_lo, row_hi))
        pieces.append(out)
        if hi:
            pieces.append(shell(axis, "hi", row_lo, row_hi))
        row_lo[axis] = 0
        row_hi[axis] = u.shape[axis]
        out = jnp.concatenate(pieces, axis=axis) if len(pieces) > 1 else out
    return out


def make_sharded_step(
    step_local: Callable,
    mesh: jax.sharding.Mesh,
    in_specs,
    out_specs,
    check_vma: bool = False,
):
    """Wrap a per-shard step (which uses exchange_pad/ppermute) via shard_map."""
    return jax.shard_map(
        step_local, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=check_vma,
    )
