"""Ensemble executor: one jitted step advances every resident simulation.

The device half of the simulation farm.  All ensemble members share one
compiled executable: the solver's parameterized local step is vmapped over a
leading *slot* axis of both the field state and the per-simulation scalar
struct (``ns3d.PARAM_KEYS`` — viscosity, dt, lid velocity, forcing), exactly
as the LM engine decodes its whole slot batch each step.  Because the serial
path (``NavierStokes3D.make_step``) threads the same f32 scalars through the
same traced step, a farm slot reproduces a serial run bit-for-bit — and so
does *chunked* stepping, a ``fori_loop`` of that step with a dynamic trip
count, which is how the farm amortizes host dispatch when no slot is due to
finish (the analogue of multi-token speculation windows in LM serving).

Two mesh placements compose (the farm's slots × shards story):

* **slot parallelism** — the slot axis spreads over a data-parallel mesh
  axis (:func:`slot_spec`); slots never interact, so the
  distributed batch is bitwise the single-device one.
* **per-slot grid decomposition** — with ``config.decomposition`` set,
  each slot's grid additionally decomposes over the named mesh axes
  (:func:`slot_field_spec`), and the vmapped step runs the
  driver's halo machinery (``exchange_pad`` / ``stencil_step_overlap``
  ppermuting over those axes) inside the same ``shard_map``.  One large
  simulation can then outgrow a single device while the farm keeps
  batching across slots.

The descriptor-generated kernels batch the same way one level down:
``GeneratedKernel.apply_batched`` vmaps the JNP template and gives the
3DBLOCK Pallas template a leading slot axis in its grid/BlockSpecs with
per-slot scalars routed through the scalar-table operand (scalar prefetch
on real TPU) — the solver-level vmap used here dispatches to exactly that
batched expansion via the generator's ``custom_vmap`` rule, so one
compiled Pallas kernel serves every resident simulation.
"""
from __future__ import annotations

import dataclasses
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.cfd.ns3d import PARAM_KEYS, CFDConfig, NavierStokes3D
from repro.obs.health import N_DIAG


def stack_trees(trees):
    """Stack a list of identically-structured pytrees on a new slot axis 0."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *trees)


# -- placement rules ---------------------------------------------------------
def _slot_guard(mesh, axis: str, n_slots: int):
    """``axis`` iff ``n_slots`` divides evenly over it (else replicated)."""
    return axis if n_slots % mesh.shape[axis] == 0 else None


def slot_spec(mesh, n_slots: int, axis: str = "data"):
    """Spec placing a leading ensemble *slot* axis over a data-parallel
    mesh axis (multi-device simulation farms: each device advances
    ``n_slots / |axis|`` resident simulations).  Divisibility-guarded: a
    non-divisible slot count stays replicated."""
    if axis not in mesh.axis_names:
        raise ValueError(f"mesh {mesh.axis_names} has no axis {axis!r}")
    return P(_slot_guard(mesh, axis, n_slots))


def validate_decomposition(decomposition, n_axes: int, mesh_axis_names,
                           slot_axis: str | None = None) -> tuple:
    """Normalize + validate a grid decomposition: returns the
    ``((array_axis, mesh_axis), ...)`` pairs, raising on a duplicate
    array axis, an out-of-range array axis, an unknown mesh axis, or a
    grid axis decomposing over the slot axis.  Shared by
    :func:`slot_field_spec` and :func:`plan_decomposition` so both enforce
    — and word — the contract identically."""
    pairs = tuple(decomposition.items() if isinstance(decomposition, dict)
                  else decomposition)
    if len({a for a, _ in pairs}) != len(pairs):
        raise ValueError(
            f"decomposition {pairs!r} maps some array axis more than "
            "once; each grid axis decomposes over at most one mesh axis")
    for a, name in pairs:
        if not 0 <= int(a) < n_axes:
            raise ValueError(
                f"decomposition names array axis {a}, but fields have "
                f"only {n_axes} grid axes")
        if name not in mesh_axis_names:
            raise ValueError(
                f"mesh {tuple(mesh_axis_names)} has no axis {name!r} "
                f"(decomposition of array axis {a})")
        if slot_axis is not None and name == slot_axis:
            raise ValueError(
                f"axis {name!r} is the slot axis; a grid axis cannot "
                "decompose over it")
    return pairs


def slot_field_spec(mesh, n_slots: int, shape: tuple, decomposition=(),
                    slot_axis: str = "slot"):
    """Spec for a slot-stacked grid field ``(n_slots, *shape)`` on a
    slots × shards farm mesh: ``P(slot_axis, <grid axes>)``.

    The two axes get different failure postures, deliberately:

    * the slot axis is *guarded* — slots never interact, so a slot count
      that does not divide over ``slot_axis`` runs replicated (correct,
      just not parallel), same as :func:`slot_spec`;
    * the grid axes *raise* — halo-exchange code inside the step ppermutes
      over the decomposition's mesh axes assuming true shards, so quietly
      replicating an indivisible grid axis would hand every device the
      full extent while the exchange still shifts it: mis-sharding, not a
      layout choice.
    """
    if slot_axis not in mesh.axis_names:
        raise ValueError(f"mesh {mesh.axis_names} has no slot axis "
                         f"{slot_axis!r}")
    pairs = validate_decomposition(decomposition, len(shape),
                                   mesh.axis_names, slot_axis=slot_axis)
    grid: list = [None] * len(shape)
    for a, name in pairs:
        a = int(a)
        if shape[a] % mesh.shape[name]:
            raise ValueError(
                f"grid extent {shape[a]} on array axis {a} is not "
                f"divisible by mesh axis {name!r} (size "
                f"{mesh.shape[name]}) — refusing to mis-shard")
        grid[a] = name
    return P(_slot_guard(mesh, slot_axis, n_slots), *grid)


def plan_decomposition(config: CFDConfig, mesh,
                       slot_axis: str | None = None
                       ) -> tuple[CFDConfig, dict]:
    """Resolve ``config.decomposition`` against the farm mesh.

    Returns ``(solver_config, active)`` where ``active`` maps array axis ->
    mesh axis for every decomposed axis whose mesh extent is > 1, and
    ``solver_config`` is ``config`` with exactly that decomposition.  Axes
    of extent 1 are dropped: a 1-shard mesh degrades to the plain
    slot-parallel fast path (same executable shape as an undecomposed
    farm) instead of threading no-op collectives through the step.

    Raises ``ValueError`` when a decomposition is requested without a
    mesh, or fails :func:`validate_decomposition` (duplicate /
    out-of-range array axis, unknown mesh axis, decomposing over the slot
    axis).  All validation runs BEFORE the extent-1 filter, so a
    mis-assembled config fails identically on a 1-shard laptop mesh and a
    real pod.
    """
    if not config.decomposition:
        return config, {}
    if mesh is None:
        raise ValueError(
            f"config.decomposition={tuple(config.decomposition)!r} asks for "
            "per-slot grid decomposition, which needs a farm mesh naming "
            "those axes (SimulationFarm(..., mesh=make_mesh((slots, shards), "
            "('slot', 'shard')))); got mesh=None")
    pairs = validate_decomposition(config.decomposition, len(config.shape),
                                   mesh.axis_names, slot_axis=slot_axis)
    active = {a: n for a, n in pairs if mesh.shape[n] > 1}
    solver_cfg = dataclasses.replace(
        config, decomposition=tuple(sorted(active.items())))
    return solver_cfg, active


def make_ensemble_step(solver: NavierStokes3D, *, mesh=None,
                       slot_axis: str = "data", n_slots: int | None = None,
                       health_window: int = 0):
    """The compiled ensemble executable for ``solver``'s configuration:
    ``run_k(state, params, k)`` advances the whole slot batch ``k`` steps
    (``k`` is a traced scalar — one compile covers every chunk size).

    With ``health_window=K > 0`` the executable becomes
    ``run_k(state, params, ring, k) -> (state, ring)``: after the ``k``
    inner steps the solver's fused ``health_diagnostics`` run ONCE on
    the chunk's final slot batch and shift into the device-side
    ``(slots, K, N_DIAG)`` ring buffer as its newest row (the oldest
    rolls off; frame column 0 is a sentinel the executor stamps with
    the absolute device step host-side when the ring is read, so the
    device carries no step counter and the dispatch ships no extra
    scalars).  Sampling per chunk — not per step — is
    what keeps the monitor's steady-state cost a vanishing fraction of
    the chunk: NaN/Inf and divergence persist in the fields, and the
    farm only acts on frames at its harvest boundaries anyway, so a
    chunk-end sample detects exactly what a per-step sample would.  The
    diagnostics are read-only reductions on the *output* of the step —
    they feed nothing back into the fields — so health-on state
    trajectories are bitwise the health-off ones, and the ring rides to
    the host only when the farm drains it at a harvest boundary (zero
    extra steady-state syncs).

    With ``mesh``, the slot axis is placed over the ``slot_axis``
    data-parallel mesh axis (vmap × shard_map): each device advances its
    slice of the resident simulations, and because slots never interact,
    the distributed batch is bitwise-identical to the single-device one.

    When the solver's domain is decomposed (slots × shards), each field is
    additionally sharded over the decomposition's mesh axes and the
    vmapped step exchanges ghost zones over them; the result is bitwise
    the serial ``GridDriver`` run of the same decomposition.
    """
    vstep = jax.vmap(solver._step_local)

    if health_window:
        vdiag = jax.vmap(solver.health_diagnostics)
        K = int(health_window)

        def run_k(state, params, ring, k):
            state = lax.fori_loop(
                0, k, lambda _, s: vstep(s, params), state)
            d = vdiag(state, params)              # (slots, N_DIAG - 1)
            # column 0 is the step stamp — written host-side on read;
            # on device it only needs to be "not the -1 blank sentinel"
            col = jnp.zeros((d.shape[0], 1), d.dtype)
            row = jnp.concatenate([col, d], axis=1)[:, None, :]
            # shift-append: newest frame last, oldest rolls off — no
            # cursor operand, rows arrive at the host already ordered
            ring = jnp.concatenate([ring[:, 1:], row.astype(ring.dtype)],
                                   axis=1)
            return state, ring
    else:
        def run_k(state, params, k):
            return lax.fori_loop(0, k, lambda _, s: vstep(s, params), state)

    if mesh is None:
        return jax.jit(run_k)

    # divisibility-guarded: a slot count that does not divide over the
    # axis runs replicated (correct, just not parallel) rather than erroring
    n = n_slots if n_slots is not None else mesh.shape[slot_axis]
    sp = slot_spec(mesh, n, axis=slot_axis)
    decomp = dict(solver.domain.decomposition)
    if decomp:
        state_spec = slot_field_spec(mesh, n, solver.config.shape, decomp,
                                     slot_axis=slot_axis)
    else:
        state_spec = sp
    if health_window:
        # the ring partitions its leading slot axis exactly like params
        in_specs = (state_spec, sp, sp, P())
        out_specs = (state_spec, sp)
    else:
        in_specs = (state_spec, sp, P())
        out_specs = state_spec
    fn = jax.shard_map(run_k, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    return jax.jit(fn)


# -- slot I/O programs -------------------------------------------------------
def _lengths(n_slots: int) -> list:
    """The padded lengths of a slot I/O round: the powers of two below
    ``n_slots``, then ``n_slots``.  A round pads to less than twice its
    members, and one compile per length serves every round size."""
    return [1 << i for i in range((n_slots - 1).bit_length())] + [n_slots]


def _padded(k: int, n_slots: int) -> int:
    """The length of :func:`_lengths` a round of ``k`` slots pads to."""
    return min(1 << (k - 1).bit_length(), n_slots)


def _frozen(v) -> np.ndarray:
    """A read-only host copy of ``v``."""
    out = np.array(v)
    out.flags.writeable = False
    return out


def _write_fresh(state, fresh, mask):
    """``state`` with one slot's ``fresh`` fields wherever the
    ``(n_slots,)`` ``mask`` is set."""
    return {k: jnp.where(mask.reshape((-1,) + (1,) * (v.ndim - 1)),
                         fresh[k].astype(v.dtype)[None], v)
            for k, v in state.items()}


def _write_rows(state, idx, rows):
    """``state`` with row ``i`` of each field of ``rows`` written to slot
    ``idx[i]``; an index past the batch is padding and is dropped."""
    return {k: v.at[idx].set(rows[k], mode="drop") for k, v in state.items()}


# the harvest's gather: the fields of the slots ``idx`` names, in order
_GATHER = jax.jit(lambda fields, idx: {k: v[idx] for k, v in fields.items()})


class EnsembleExecutor:
    """Slot-stacked state + the single jitted step that advances it.

    Owns no scheduling policy: slots are written/read by index, every step
    advances all of them (idle slots compute garbage that the farm masks on
    the host — the standard padding-batch trade from LM serving).
    """

    def __init__(self, config: CFDConfig, n_slots: int,
                 solver: NavierStokes3D | None = None, run_k=None,
                 mesh=None, slot_axis: str = "data", telemetry=None,
                 health_window: int = 0):
        from repro import obs

        self.tel = obs.resolve(telemetry)
        solver_cfg, decomp = plan_decomposition(config, mesh,
                                                slot_axis=slot_axis)
        self.config = config
        self.decomposition = decomp    # active per-slot grid decomposition
        self.n_slots = n_slots
        self.mesh = mesh
        self.slot_axis = slot_axis
        self.health_window = int(health_window)
        self.solver = solver if solver is not None else NavierStokes3D(
            solver_cfg, mesh if decomp else None)
        self._run_k = run_k if run_k is not None else make_ensemble_step(
            self.solver, mesh=mesh, slot_axis=slot_axis, n_slots=n_slots,
            health_window=self.health_window)
        fresh = self.solver.init_state()
        self._fresh = fresh            # per-slot initial state (unbatched)
        self.state = stack_trees([fresh] * n_slots)
        if mesh is not None:
            # pin the resident batch to its farm layout up front: slot axis
            # over `slot_axis`, grid axes over the active decomposition —
            # admissions then scatter into place instead of re-laying-out
            spec = (slot_field_spec(mesh, n_slots, solver_cfg.shape, decomp,
                                    slot_axis=slot_axis)
                    if decomp else slot_spec(mesh, n_slots, axis=slot_axis))
            self.state = jax.device_put(self.state,
                                        NamedSharding(mesh, spec))
            # stacked host rows of a readmission round: grid axes as one
            # slot's fields, the round axis replicated
            self._row_sharding = NamedSharding(
                mesh, P(None, *self.solver.field_pspec) if decomp else P())
        else:
            # committed like the step's output, so the slot I/O programs
            # compiled before the first step serve every later round
            self._row_sharding = jax.sharding.SingleDeviceSharding(
                next(iter(fresh["vx"].devices())))
            self.state = jax.device_put(self.state, self._row_sharding)
        # the fields the step writes; the rest (the wall masks) pass
        # through it unchanged, so harvests take them from host copies
        # made when each slot was written
        self._dynamic = tuple(k for k in self.state
                              if k in self.solver.FIELDS)
        self._static_fresh = {k: _frozen(v) for k, v in fresh.items()
                              if k not in self._dynamic}
        self._static = [self._static_fresh] * n_slots
        self._fresh_write = None  # compiled at the first slot I/O
        self._rows_write = None   # compiled at the first host state
        # device-side health ring: (slots, K, N_DIAG), shift-append (row
        # K-1 is the newest frame).  Column 0 is the device-step stamp:
        # -1 = blank sentinel on device; `read_health` overwrites it from
        # `_ring_steps`, the host-side record of each write's chunk-end
        # step — the device ships no step counter at all.  The ring
        # shards over the slot axis exactly like params.
        self.health_ring = None
        self.steps_taken = 0
        self._ring_steps: deque | None = None
        if self.health_window:
            K = self.health_window
            # step column -1 = "no frame recorded yet" sentinel
            blank = jnp.zeros((K, N_DIAG), jnp.float32).at[:, 0].set(-1.0)
            ring = jnp.broadcast_to(blank, (n_slots, K, N_DIAG))
            if mesh is not None:
                ring = jax.device_put(ring, NamedSharding(
                    mesh, slot_spec(mesh, n_slots, axis=slot_axis)))
            self.health_ring = ring
            self._ring_steps = deque(maxlen=K)
        # per-slot scalars: host-authoritative (like the engine's slot
        # lengths), mirrored to a device struct only when admission dirties
        # them — steps between admissions ship nothing host->device
        self.params = {k: np.zeros((n_slots,), np.float32) for k in PARAM_KEYS}
        self.params["dt"][:] = np.float32(config.dt)   # idle slots stay finite
        self._params_dev = None
        self._ke = jax.jit(jax.vmap(
            lambda st: 0.5 * sum(jnp.mean(st[f] ** 2)
                                 for f in ("vx", "vy", "vz"))))

        # residual norm between two consecutive states: per-slot
        # ||u^{n+1} - u^n||_inf / dt over the velocity fields.  Runs OUTSIDE
        # the compiled ensemble step (on two state snapshots) so enabling
        # residual-based termination cannot perturb the step's numerics —
        # under jit on sharded inputs the max reduces globally across
        # shards without any explicit collective.
        def _resid(new, old, dt):
            per_slot = jnp.stack([
                jnp.max(jnp.abs(new[f] - old[f]),
                        axis=tuple(range(1, new[f].ndim)))
                for f in ("vx", "vy", "vz")])
            return jnp.max(per_slot, axis=0) / jnp.maximum(dt, 1e-30)

        self._resid = jax.jit(_resid)

    # -- slot I/O -------------------------------------------------------------
    def state_template(self) -> dict:
        """Host zeros with one slot's field shapes/dtypes — the restore
        template for spilled-to-disk evictions (no device gather: only
        metadata of the fresh per-slot state is read)."""
        return {k: np.zeros(v.shape, v.dtype)
                for k, v in self._fresh.items()}

    def slot_sharding(self) -> jax.sharding.Sharding | None:
        """Sharding of ONE slot's fields (grid axes only) on a decomposed
        farm — what evict must gather from and readmit must scatter back
        to; None when slots are not grid-decomposed."""
        if self.mesh is None or not self.decomposition:
            return None
        return NamedSharding(self.mesh, self.solver.field_pspec)

    def check_state(self, state: dict) -> dict:
        """``state`` as host arrays in the batch's dtypes, checked against
        one slot's fields: the same names and shapes, or ValueError.  The
        farm checks each readmission on its own before batching a round,
        so a mis-shaped one fails alone."""
        if set(state) != set(self.state):
            raise ValueError(
                f"state fields {sorted(state)} do not match the farm's "
                f"{sorted(self.state)}")
        out = {}
        for k, full in self.state.items():
            v = np.asarray(state[k], dtype=full.dtype)
            if v.shape != full.shape[1:]:
                raise ValueError(f"state field {k!r} has shape {v.shape}, "
                                 f"a slot holds {full.shape[1:]}")
            out[k] = v
        return out

    def _io_programs(self):
        """Compile the fresh-admission update and the harvest gather of
        every round length at the first slot I/O call (a warm-up's), so no
        later round compiles."""
        if self._fresh_write is not None:
            return
        out = {k: v.sharding for k, v in self.state.items()}
        fresh = jax.jit(_write_fresh, donate_argnums=0, out_shardings=out)
        fresh.lower(self.state, self._fresh,
                    np.zeros(self.n_slots, bool)).compile()
        dynamic = {k: self.state[k] for k in self._dynamic}
        for b in _lengths(self.n_slots):
            _GATHER.lower(dynamic, np.zeros(b, np.int32)).compile()
        self._fresh_write = fresh

    def _rows_program(self):
        """The readmission scatter, compiled for every padded round length
        at the first round that carries a host state."""
        if self._rows_write is None:
            out = {k: v.sharding for k, v in self.state.items()}
            rows = jax.jit(_write_rows, donate_argnums=0, out_shardings=out)
            for b in _lengths(self.n_slots):
                rows.lower(self.state, np.zeros(b, np.int32), {
                    k: jax.ShapeDtypeStruct((b,) + v.shape[1:], v.dtype,
                                            sharding=self._row_sharding)
                    for k, v in self.state.items()}).compile()
            self._rows_write = rows
        return self._rows_write

    def write_slots(self, admits: list):
        """Admit a round of simulations: install their parameters and
        (re)set their fields, in one update of the resident batch.

        ``admits`` holds ``(slot, params, state)`` triples.  ``params``
        are the per-slot scalars, read on the host.  ``state=None`` writes
        the case's fresh initial fields (a new run): one donated ``where``
        over an ``(n_slots,)`` mask writes every fresh slot of the round.
        A host state dict readmits an evicted simulation: the round's host
        states are stacked per field, placed with one host->device copy
        per field (on a decomposed farm straight to the grid shards) and
        scattered in one more dispatch, the round padded to a length of
        :func:`_lengths` whose padding is dropped.  The span
        ``ensemble.write_slots`` carries ``members``, ``transfers`` (the
        host->device field copies) and their ``bytes``.
        """
        fresh = [slot for slot, _, state in admits if state is None]
        hosted = [(slot, self.check_state(state))
                  for slot, _, state in admits if state is not None]
        rows = idx = None
        if hosted:
            b = _padded(len(hosted), self.n_slots)
            pad = [hosted[-1][1]] * (b - len(hosted))
            idx = np.full(b, self.n_slots, np.int32)
            idx[:len(hosted)] = [slot for slot, _ in hosted]
            states = [state for _, state in hosted] + pad
            rows = {k: np.stack([s[k] for s in states]) for k in self.state}
        with self.tel.section(
                "ensemble.write_slots", members=len(admits),
                transfers=len(rows) if rows else 0,
                bytes=sum(v.nbytes for v in rows.values()) if rows else 0):
            self._io_programs()
            if fresh:
                mask = np.zeros(self.n_slots, bool)
                mask[fresh] = True
                self.state = self._fresh_write(self.state, self._fresh,
                                               mask)
            if rows:
                program = self._rows_program()
                self.state = program(
                    self.state, idx, jax.device_put(rows, self._row_sharding))
            # the health ring is deliberately NOT reset here: its step
            # column is the executor's monotonic step counter, so the
            # monitor filters a previous occupant's rows by admit-time
            # device step — admission stays a single state update
            self.tel.fence(self.state)
        for slot in fresh:
            self._static[slot] = self._static_fresh
        for slot, state in hosted:
            self._static[slot] = {k: _frozen(state[k])
                                  for k in self._static_fresh}
        for slot, params, _ in admits:
            for k in PARAM_KEYS:
                self.params[k][slot] = np.float32(params[k])
        self._params_dev = None

    def write_slot(self, slot: int, params: dict, state: dict | None = None):
        """Admit one simulation: :meth:`write_slots` with one member."""
        with self.tel.section("ensemble.write_slot"):
            self.write_slots([(slot, params, state)])

    def read_slots(self, slots: list) -> list:
        """Host copies of a round's simulations, in the order of ``slots``:
        one device->host copy per field the step writes, for all of them.

        A gather of a padded length (:func:`_lengths`) picks the round's
        slots, so a round of one moves one member's fields and a round of
        most of the batch the whole batch's.  The masks, which the step
        never writes, come from the host copies made at admission.  The
        span ``ensemble.read_slots`` carries ``members``, ``transfers``
        and their ``bytes``.
        """
        if not slots:
            return []
        self._io_programs()
        b = _padded(len(slots), self.n_slots)
        idx = np.full(b, slots[0], np.int32)
        idx[:len(slots)] = slots
        fields = {k: self.state[k] for k in self._dynamic}
        nbytes = sum(b * v.nbytes // v.shape[0] for v in fields.values())
        with self.tel.section("ensemble.read_slots", members=len(slots),
                              transfers=len(fields), bytes=nbytes):
            got = _GATHER(fields, idx)
            for v in got.values():
                v.copy_to_host_async()
            host = {k: np.asarray(v) for k, v in got.items()}
        return [{k: host[k][i] if k in host else self._static[slot][k]
                 for k in self.state} for i, slot in enumerate(slots)]

    def read_slot(self, slot: int) -> dict:
        """Host copy of one simulation's fields: :meth:`read_slots` with
        one member."""
        with self.tel.section("ensemble.read_slot"):
            return self.read_slots([slot])[0]

    def clear_slot(self, slot: int):
        """Park a freed slot on benign parameters (finite garbage compute)."""
        for k in PARAM_KEYS:
            self.params[k][slot] = np.float32(
                self.config.dt if k == "dt" else 0.0)
        self._params_dev = None

    # -- stepping -------------------------------------------------------------
    def _device_params(self) -> dict:
        if self._params_dev is None:
            self._params_dev = {k: jnp.asarray(v)
                                for k, v in self.params.items()}
        return self._params_dev

    def step_args(self, k: int = 1) -> tuple:
        """The exact argument tuple ``_run_k`` is dispatched with — the
        perf layer lowers ``_run_k(*step_args(1))`` to cost-model the
        farm step whatever the health signature."""
        if self.health_ring is not None:
            return (self.state, self._device_params(), self.health_ring,
                    jnp.int32(k))
        return (self.state, self._device_params(), jnp.int32(k))

    def step_many(self, k: int):
        """Advance the whole slot batch ``k`` device steps in one dispatch."""
        out = self._run_k(*self.step_args(k))
        if self.health_ring is not None:
            self.state, self.health_ring = out
            # the frame sampled this dispatch is the chunk-end step
            self._ring_steps.append(self.steps_taken + int(k) - 1)
        else:
            self.state = out
        self.steps_taken += int(k)

    def read_health(self) -> np.ndarray:
        """Host copy of the ``(slots, K, N_DIAG)`` health ring — THE one
        device->host sync of the health path, issued by the farm only at
        ``check_steady_every`` harvest boundaries.  Column 0 of the last
        ``len(_ring_steps)`` rows is stamped with each frame's absolute
        device step from the host-side write record; older rows keep the
        -1 blank sentinel."""
        # np.array (not asarray): the zero-copy view of a CPU jax array
        # is read-only, and the step stamp writes into column 0
        rings = np.array(self.health_ring)
        if self._ring_steps:
            rings[:, -len(self._ring_steps):, 0] = np.asarray(
                self._ring_steps, np.float32)
        return rings

    def step(self):
        """One device step for the whole slot batch."""
        self.step_many(1)

    def kinetic_energy(self) -> np.ndarray:
        """(n_slots,) per-slot kinetic energy (steady-state detection)."""
        return np.asarray(self._ke(self.state))

    def residuals(self, prev_state) -> np.ndarray:
        """(n_slots,) per-slot ``||u_now - u_prev||_inf / dt`` — the
        steady-state residual of the resident batch relative to the
        ``prev_state`` snapshot (normally the state one device step ago)."""
        return np.asarray(self._resid(self.state, prev_state,
                                      self._device_params()["dt"]))
