"""CaCUDA code generator, retargeted from CUDA templates to Pallas/XLA.

The paper's generator parses kernel descriptors and expands optimized CUDA
templates (shared-memory staging, 3D block tiling, axis streaming) so that
application authors write only the per-cell update.  Here the same descriptor
drives two templates:

* ``3DBLOCK`` — a ``pl.pallas_call`` whose BlockSpecs are derived from the
  descriptor: cached (``CACHED=YES``) read variables are staged HBM->VMEM as
  halo-expanded ``Element`` blocks (``tile + stencil``), outputs as bare
  ``tile`` blocks.  This is the TPU analogue of the paper's shared-memory
  tile staging; the MXU/VPU alignment rules replace CUDA warp rules.

* ``JNP`` — a fused pure-``jnp`` expansion of the same body (shifted slices
  of the padded array).  It is the oracle for kernel tests, the
  shape-polymorphic kernel used for boundary shells in overlap mode, and the
  default XLA path on every backend (3DBLOCK does not lower for the TPU
  yet: ``tests/test_chip_compile.py``).

The *kernel body* the user writes is a function ``body(ctx) -> dict`` where
``ctx[name]`` is a :class:`FieldView` supporting ``.at(dx, dy, dz)`` shifted
reads — the moral equivalent of the generated CUDA macros that CaCUDA emitted
for indexing shared memory.  The same body traces through both templates.

Runtime parameters split two ways in the 3DBLOCK template:

* **Python/numpy scalars** are baked into the kernel as trace-time literals
  (the original behavior — fine for geometry like ``h`` that is static per
  compiled executable).
* **Array-valued scalars** (``jax.Array`` or tracers, e.g. the per-simulation
  ``nu``/``dt`` the simulation farm threads through its vmapped step) are
  packed into a ``(rows, n_params)`` *scalar table* operand: row 0 for a
  single simulation, row ``s`` for slot ``s`` of a batched call.  On real TPU
  hardware the table rides the scalar-prefetch lane
  (``pltpu.PrefetchScalarGridSpec`` — SMEM, available before the grid body
  runs); in interpret mode / on other backends it is an ordinary leading
  operand whose BlockSpec selects the slot's row.  Either way ONE compiled
  kernel serves every scalar assignment — admitting a new parameter variant
  into the farm never recompiles, and a ``jax.vmap`` over the call (the
  ensemble executor's slot axis) dispatches to a slot-indexed batched grid
  via ``jax.custom_batching.custom_vmap``.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Mapping

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.descriptor import Intent, StencilDescriptor


def _split_params(params: dict) -> tuple[dict, dict]:
    """Partition runtime parameters into (literal, array-valued).

    Array-valued covers concrete ``jax.Array``s AND tracers (jit/vmap);
    Python and numpy scalars stay literals, preserving the direct-call
    behavior of eager kernel invocations in tests and notebooks.
    """
    literal, arr = {}, {}
    for k, v in params.items():
        (arr if isinstance(v, jax.Array) else literal)[k] = v
    return literal, arr


def element_block_spec(block_shape, index_map) -> pl.BlockSpec:
    """BlockSpec whose ``index_map`` returns *element* offsets.

    This is how the 3DBLOCK template expresses halo-expanded overlapping
    windows (tile + stencil) staged into VMEM: every block dim is a
    ``pl.Element``, so the index map returns element (not block) offsets.
    """
    return pl.BlockSpec(tuple(pl.Element(b) for b in block_shape), index_map)


class FieldView:
    """Shifted-stencil accessor over a halo-padded array (or VMEM block)."""

    __slots__ = ("arr", "halo_lo", "halo_hi")

    def __init__(self, arr, halo_lo, halo_hi):
        self.arr = arr
        self.halo_lo = halo_lo
        self.halo_hi = halo_hi

    def at(self, dx: int = 0, dy: int = 0, dz: int = 0) -> jnp.ndarray:
        off = (dx, dy, dz)
        idx = []
        for a, o in enumerate(off):
            lo, hi = self.halo_lo[a], self.halo_hi[a]
            if not -lo <= o <= hi:
                raise ValueError(
                    f"stencil offset {off} exceeds declared radii "
                    f"(lo={self.halo_lo}, hi={self.halo_hi})"
                )
            stop = self.arr.shape[a] - hi + o
            idx.append(slice(lo + o, stop))
        return self.arr[tuple(idx)]

    @property
    def c(self) -> jnp.ndarray:
        return self.at(0, 0, 0)


class KernelContext(Mapping):
    """What the kernel body sees: field views + runtime parameters."""

    def __init__(self, views: dict[str, FieldView], params: dict[str, Any]):
        self._views = views
        self._params = params

    def __getitem__(self, name: str) -> FieldView:
        return self._views[name]

    def __iter__(self):
        return iter(self._views)

    def __len__(self):
        return len(self._views)

    def param(self, name: str):
        return self._params[name]


@dataclasses.dataclass
class GeneratedKernel:
    """A compiled-from-descriptor kernel, callable on padded input arrays.

    ``__call__(arrays, **params) -> dict[name, interior array]`` where
    ``arrays[name]`` for read variables is the *padded* local array
    (interior + stencil ghosts) and outputs are interior-shaped.
    """

    desc: StencilDescriptor
    body: Callable[[KernelContext], dict[str, jnp.ndarray]]
    template: str
    interpret: bool = False

    def __post_init__(self):
        self._halo_lo = self.desc.halo_lo
        self._halo_hi = self.desc.halo_hi
        # custom_vmap entry points, one per (literal params, array-param
        # names) signature — the vmap rule must be installed once per
        # callable, and the literal half of the params is baked into it
        self._entry_cache: dict[tuple, Any] = {}

    # ---- JNP template -----------------------------------------------------
    def _apply_jnp(self, arrays: dict[str, jnp.ndarray], params: dict[str, Any]):
        views = {}
        for name in self.desc.inputs:
            cached = name in self.desc.cached_inputs
            hl = self._halo_lo if cached else (0, 0, 0)
            hh = self._halo_hi if cached else (0, 0, 0)
            views[name] = FieldView(arrays[name], hl, hh)
        out = self.body(KernelContext(views, params))
        missing = set(self.desc.outputs) - set(out)
        if missing:
            raise ValueError(f"kernel body did not produce outputs: {sorted(missing)}")
        return {k: out[k] for k in self.desc.outputs}

    # ---- 3DBLOCK (Pallas) template ----------------------------------------
    def _scalar_table(self, arr: dict[str, Any], nslots: int | None):
        """Pack array-valued params into a ``(rows, n)`` table.

        Returns ``(names, dtypes, table)`` — column order follows the
        descriptor's parameter declaration; each column is cast to the
        promoted table dtype and cast back on read inside the kernel.
        Rows: 1 (unbatched) or ``nslots`` (one row per slot; shared
        scalars broadcast so every slot reads its own row).
        """
        declared = sorted((k for k in arr if k in self.desc.parameters),
                          key=self.desc.param_index)
        extra = sorted(set(arr) - set(declared))
        names = tuple(declared + extra)
        dtypes = tuple(jnp.asarray(arr[k]).dtype for k in names)
        tdt = jnp.result_type(*dtypes)
        cols = []
        for k in names:
            v = jnp.asarray(arr[k]).astype(tdt)
            cols.append(jnp.broadcast_to(v, (nslots,)) if nslots is not None
                        else jnp.reshape(v, ()))
        table = jnp.stack(cols, axis=-1)
        if nslots is None:
            table = table[None]                      # (1, n)
        return names, dtypes, table

    def _apply_pallas(self, arrays: dict[str, jnp.ndarray],
                      params: dict[str, Any], *, batched: bool = False):
        """The 3DBLOCK expansion; ``batched`` adds a leading slot axis to
        the grid and every BlockSpec so one ``pallas_call`` advances all
        resident simulations (the ensemble-executor form).

        Array-valued params ride a scalar table operand (row per slot when
        batched — scalar prefetch on real TPU, a leading SMEM-style operand
        in interpret mode); literal params are baked at trace time.
        """
        desc = self.desc
        literal, arr = _split_params(params)
        tx, ty, tz = desc.tile
        hl, hh = self._halo_lo, self._halo_hi
        first = arrays[desc.inputs[0]]
        nslots = first.shape[0] if batched else None
        space = first.shape[1:] if batched else first.shape
        interior = tuple(
            s - (lo + hi) for s, lo, hi in zip(space, hl, hh)
        ) if desc.inputs[0] in desc.cached_inputs else space
        nx, ny, nz = interior
        if nx % tx or ny % ty or nz % tz:
            raise ValueError(
                f"interior {interior} not divisible by tile {desc.tile}; "
                f"use the autotuner or the JNP template"
            )
        grid = (nx // tx, ny // ty, nz // tz)
        if batched:
            grid = (nslots,) + grid

        if arr:
            tab_names, tab_dtypes, table = self._scalar_table(arr, nslots)
        else:
            tab_names, tab_dtypes, table = (), (), None
        # real TPU hardware prefetches the table into SMEM ahead of the
        # grid body; everywhere else (interpret mode, CPU/GPU lowering)
        # it is a plain leading operand whose BlockSpec picks the row
        use_prefetch = (table is not None and not self.interpret
                        and jax.default_backend() == "tpu")

        def slotted(block, index_map, element):
            """Prepend the slot dim (block 1, offset = slot index).

            Index maps take ``*_`` so the scalar-prefetch grid spec —
            which appends the scalar refs to every index-map call — and
            the plain grid agree on one signature.
            """
            if not batched:
                return (element_block_spec(block, index_map) if element
                        else pl.BlockSpec(block, index_map))
            block = (1,) + block
            index_map = lambda b, *g, _m=index_map: (b,) + _m(*g)
            return (element_block_spec(block, index_map) if element
                    else pl.BlockSpec(block, index_map))

        in_specs = []
        in_arrays = []
        for name in desc.inputs:
            if name in desc.cached_inputs:
                # halo-expanded overlapping window staged into VMEM — the
                # shared-memory tile of the paper's 3DBLOCK template
                spec = slotted(
                    (tx + hl[0] + hh[0], ty + hl[1] + hh[1], tz + hl[2] + hh[2]),
                    lambda i, j, k, *_: (i * tx, j * ty, k * tz), element=True)
            else:
                spec = slotted((tx, ty, tz), lambda i, j, k, *_: (i, j, k),
                               element=False)
            in_specs.append(spec)
            in_arrays.append(arrays[name])

        out_spec = slotted((tx, ty, tz), lambda i, j, k, *_: (i, j, k),
                           element=False)
        out_names = desc.outputs
        out_shape = ((nslots,) + interior) if batched else interior
        out_shapes = [jax.ShapeDtypeStruct(out_shape, arrays[n].dtype
                                           if n in arrays else first.dtype)
                      for n in out_names]

        def pallas_body(*refs):
            if table is not None:
                tab_ref, refs = refs[0], refs[1:]
                # prefetch hands the WHOLE table to every grid instance
                # (slot row selected by program id); the operand fallback
                # already blocked it down to this slot's (1, n) row
                row = (pl.program_id(0) if (use_prefetch and batched) else 0)
                run = {k: tab_ref[row, i].astype(dt)
                       for i, (k, dt) in enumerate(zip(tab_names, tab_dtypes))}
            else:
                run = {}
            body_params = {**literal, **run}
            in_refs = refs[: len(in_arrays)]
            out_refs = refs[len(in_arrays):]
            views = {}
            for name, ref in zip(desc.inputs, in_refs):
                blk = ref[...]
                if batched:
                    blk = blk[0]  # drop the slot dim inside the block
                cached = name in desc.cached_inputs
                views[name] = FieldView(
                    blk, hl if cached else (0, 0, 0), hh if cached else (0, 0, 0)
                )
            out = self.body(KernelContext(views, body_params))
            for name, ref in zip(out_names, out_refs):
                val = out[name][None] if batched else out[name]
                ref[...] = val.astype(ref.dtype)

        if use_prefetch:
            from jax.experimental.pallas import tpu as pltpu

            call = pl.pallas_call(
                pallas_body,
                grid_spec=pltpu.PrefetchScalarGridSpec(
                    num_scalar_prefetch=1,
                    grid=grid,
                    in_specs=in_specs,
                    out_specs=[out_spec] * len(out_names),
                ),
                out_shape=out_shapes,
            )
            results = call(table, *in_arrays)
        else:
            operands = tuple(in_arrays)
            if table is not None:
                n = len(tab_names)
                tab_spec = pl.BlockSpec(
                    (1, n),
                    (lambda s, *_: (s, 0)) if batched
                    else (lambda *_: (0, 0)))
                in_specs = [tab_spec] + in_specs
                operands = (table,) + operands
            if self.interpret:
                # interpret mode inlines the kernel into the surrounding
                # XLA program, where fusion/FMA contraction may associate
                # differently with and without a leading slot axis.  A real
                # pallas_call is an opaque custom call; pinning the kernel
                # boundary restores that semantics, keeping batched (farm)
                # programs bitwise-identical to their serial counterparts.
                operands = jax.lax.optimization_barrier(operands)
            results = pl.pallas_call(
                pallas_body,
                grid=grid,
                in_specs=in_specs,
                out_specs=[out_spec] * len(out_names),
                out_shape=out_shapes,
                interpret=self.interpret,
            )(*operands)
            if self.interpret:
                results = jax.lax.optimization_barrier(results)
        if len(out_names) == 1:
            results = (results,) if not isinstance(results, (list, tuple)) else results
        return dict(zip(out_names, results))

    def _pallas_entry(self, lit_key: tuple, arr_names: tuple):
        """The vmappable 3DBLOCK entry point for one params signature.

        A ``custom_vmap``-wrapped call: unbatched it is the plain operand
        -table ``_apply_pallas``; under ``jax.vmap`` (the ensemble
        executor's slot axis) the rule re-expands to the slot-grid batched
        ``pallas_call`` with one scalar-table row per slot, instead of
        leaving pallas's generic batching rule to guess.  Cached per
        (literal params, array-param names) so the vmap rule is installed
        once per signature.
        """
        entry = self._entry_cache.get((lit_key, arr_names))
        if entry is not None:
            return entry
        literal = dict(lit_key)

        @jax.custom_batching.custom_vmap
        def call(arrays, aparams):
            return self._apply_pallas(arrays, {**literal, **aparams})

        @call.def_vmap
        def _rule(axis_size, in_batched, arrays, aparams):
            arr_b, par_b = in_batched
            arrays = {
                k: v if arr_b[k]
                else jnp.broadcast_to(v, (axis_size,) + v.shape)
                for k, v in arrays.items()}
            aparams = {
                k: v if par_b[k]
                else jnp.broadcast_to(v, (axis_size,) + jnp.shape(v))
                for k, v in aparams.items()}
            out = self._apply_pallas(arrays, {**literal, **aparams},
                                     batched=True)
            return out, {k: True for k in out}

        self._entry_cache[(lit_key, arr_names)] = call
        return call

    # ---- batched (slot-axis) templates ------------------------------------
    def _apply_jnp_batched(self, arrays, params, batched_params):
        batched = {k: v for k, v in params.items() if k in batched_params}
        static = {k: v for k, v in params.items() if k not in batched_params}

        def fn(a, bp):
            return self._apply_jnp(a, {**static, **bp})

        return jax.vmap(fn, in_axes=(0, 0))(arrays, batched)

    def apply_batched(self, arrays: dict[str, jnp.ndarray],
                      batched_params: frozenset | tuple = (), **params):
        """Apply the kernel over a leading slot (batch) axis of every array.

        ``batched_params`` names runtime parameters that also carry the slot
        axis (per-simulation scalars, e.g. viscosity); the rest are shared.
        The JNP template vmaps the fused expansion; the 3DBLOCK template adds
        the slot axis to its grid/BlockSpecs and routes per-slot scalars
        through the scalar table — one table row per slot (scalar prefetch
        on real TPU), so heterogeneous physics shares one compiled kernel.
        """
        for p in self.desc.parameters:
            if p not in params:
                raise ValueError(f"missing runtime parameter {p!r}")
        if self.template == "JNP":
            return self._apply_jnp_batched(arrays, params,
                                           frozenset(batched_params))
        bad = [k for k in batched_params
               if not isinstance(params.get(k), jax.Array)]
        if bad:
            raise ValueError(
                f"batched parameters must be array-valued with a leading "
                f"slot axis; got non-array values for {sorted(bad)}")
        return self._apply_pallas(arrays, params, batched=True)

    def __call__(self, arrays: dict[str, jnp.ndarray], **params):
        for p in self.desc.parameters:
            if p not in params:
                raise ValueError(f"missing runtime parameter {p!r}")
        if self.template == "JNP":
            return self._apply_jnp(arrays, params)
        literal, arr = _split_params(params)
        if not arr:
            # all-literal calls keep the original direct expansion (and
            # pallas's own batching rule under vmap, which interpret mode
            # executes identically to the slot-grid form)
            return self._apply_pallas(arrays, params)
        entry = self._pallas_entry(
            tuple(sorted(literal.items())), tuple(sorted(arr)))
        return entry(arrays, {k: jnp.asarray(v) for k, v in arr.items()})

    def describe(self) -> str:
        """Human-readable summary of the generated kernel (the 'emitted code')."""
        d = self.desc
        hx, hy, hz = d.halo_width
        lines = [
            f"kernel {d.name} [{self.template}] tile={d.tile} stencil={d.stencil}",
            f"  grid = interior / tile ; VMEM/block ~ {d.vmem_block_bytes()} B (f32)",
        ]
        for g in d.variables:
            stage = "VMEM halo-block" if (g.cached and g.intent.is_read) else "VMEM tile"
            lines.append(
                f"  {','.join(g.names):24s} intent={g.intent.value:13s} {stage}"
            )
        for p in d.parameters:
            lines.append(f"  {p:24s} runtime parameter "
                         f"(literal if Python scalar, scalar-table operand "
                         f"if traced/array)")
        return "\n".join(lines)


def generate(
    desc: StencilDescriptor,
    body: Callable[[KernelContext], dict[str, jnp.ndarray]],
    *,
    template: str | None = None,
    interpret: bool = False,
) -> GeneratedKernel:
    """Expand ``desc`` + ``body`` into an executable kernel.

    ``template=None`` uses the descriptor's TYPE (``3DBLOCK`` -> Pallas).
    ``interpret=True`` runs the Pallas template through the interpreter
    (CPU-correctness mode used by the test suite).
    """
    tmpl = template or desc.type
    if tmpl not in ("3DBLOCK", "JNP"):
        raise ValueError(f"unknown template {tmpl!r}")
    return GeneratedKernel(desc=desc, body=body, template=tmpl, interpret=interpret)


def generate_pair(desc, body):
    """(pallas_interpret, jnp_oracle) pair for validation tests."""
    return (
        generate(desc, body, template="3DBLOCK", interpret=True),
        generate(desc, body, template="JNP"),
    )
