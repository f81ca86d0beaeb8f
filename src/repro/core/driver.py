"""The driver component: domain decomposition over the TPU mesh.

In Cactus the *driver thorn* (PUGH/Carpet) sets up storage, partitions the
grid between processes, and owns inter-process communication.  Here the
driver owns the named JAX mesh, builds the halo AxisSpecs for stencil
kernels, allocates sharded fields, and wraps local step functions in
``shard_map`` so that application code (the CFD solver) is written purely in
terms of local blocks + ghost zones — as in the paper.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.halo import AxisSpec, BCRule, exchange_pad


@dataclasses.dataclass(frozen=True)
class Domain:
    """Global regular grid: extent, spacing, decomposition, boundaries."""

    shape: tuple[int, int, int]
    spacing: tuple[float, float, float] = (1.0, 1.0, 1.0)
    origin: tuple[float, float, float] = (0.0, 0.0, 0.0)
    # array axis -> mesh axis name (axes absent are not decomposed)
    decomposition: Mapping[int, str] = dataclasses.field(default_factory=dict)
    periodic: tuple[bool, bool, bool] = (False, False, False)

    def pspec(self) -> P:
        parts = [self.decomposition.get(a) for a in range(3)]
        return P(*parts)


class GridDriver:
    """Owns mesh + domain; hands out shardings, axis specs, sharded steps."""

    def __init__(self, domain: Domain, mesh: jax.sharding.Mesh | None = None):
        self.domain = domain
        self.mesh = mesh
        if mesh is not None:
            for a, name in domain.decomposition.items():
                if name not in mesh.axis_names:
                    raise ValueError(f"mesh has no axis {name!r} for array axis {a}")
                if domain.shape[a] % mesh.shape[name]:
                    raise ValueError(
                        f"global extent {domain.shape[a]} on axis {a} not divisible "
                        f"by mesh axis {name!r} (size {mesh.shape[name]})"
                    )
        elif domain.decomposition:
            raise ValueError("decomposed domain requires a mesh")

    # -- geometry ------------------------------------------------------------
    @property
    def local_shape(self) -> tuple[int, int, int]:
        s = list(self.domain.shape)
        if self.mesh is not None:
            for a, name in self.domain.decomposition.items():
                s[a] //= self.mesh.shape[name]
        return tuple(s)

    def sharding(self) -> jax.sharding.Sharding | None:
        if self.mesh is None:
            return None
        return NamedSharding(self.mesh, self.domain.pspec())

    def axis_specs(
        self,
        bc_lo: Sequence[BCRule | None] = (None, None, None),
        bc_hi: Sequence[BCRule | None] = (None, None, None),
    ) -> tuple[AxisSpec, AxisSpec, AxisSpec]:
        """Halo AxisSpecs for the three array axes (for exchange_pad)."""
        return tuple(
            AxisSpec(
                array_axis=a,
                mesh_axis=self.domain.decomposition.get(a),
                periodic=self.domain.periodic[a],
                bc_lo=bc_lo[a],
                bc_hi=bc_hi[a],
            )
            for a in range(3)
        )

    # -- storage ------------------------------------------------------------
    def coords(self) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
        """Global cell-center coordinate arrays (sharded like fields).

        Built already sharded: each device computes only its own block,
        so no device ever holds a whole global grid.
        """
        axes = [
            self.domain.origin[a] + (np.arange(self.domain.shape[a]) + 0.5) * self.domain.spacing[a]
            for a in range(3)
        ]

        def grids():
            return tuple(jnp.meshgrid(*[jnp.asarray(x) for x in axes],
                                      indexing="ij"))

        sh = self.sharding()
        if sh is None:
            return grids()
        return jax.jit(grids, out_shardings=(sh,) * 3)()

    def allocate(self, names: Sequence[str], init=0.0, dtype=jnp.float32) -> dict:
        """Constant-filled fields, created directly in their sharding."""
        sh = self.sharding()
        return {n: jnp.full(self.domain.shape, init, dtype=dtype, device=sh)
                for n in names}

    # -- execution ----------------------------------------------------------
    def sharded_step(self, step_local: Callable, n_fields_out: int | None = None):
        """Wrap a per-shard ``state -> state`` function with shard_map + jit.

        ``step_local`` sees local blocks and may call ``exchange_pad`` /
        ``stencil_step_overlap`` with this driver's axis specs.  Without a
        mesh it is jitted directly (single-device path used by unit tests).
        """
        if self.mesh is None:
            return jax.jit(step_local)
        spec = self.domain.pspec()
        mapped = jax.shard_map(
            step_local,
            mesh=self.mesh,
            in_specs=spec,
            out_specs=spec,
            check_vma=False,
        )
        return jax.jit(mapped)

    def sharded_step_tree(self, step_local: Callable, example_state,
                          example_params=None) -> Callable:
        """Like sharded_step but for a pytree state (dict of fields).

        ``example_params``: optional pytree of replicated scalars passed as a
        second *traced* argument (``step(state, params)``).  Keeping runtime
        parameters out of the closure means the compiled code is identical to
        the ensemble farm's vmapped step, where they are batched arguments.
        """
        if self.mesh is None:
            return jax.jit(step_local)
        spec = self.domain.pspec()
        tree_spec = jax.tree_util.tree_map(lambda _: spec, example_state)
        in_specs = (tree_spec,)
        if example_params is not None:
            in_specs += (jax.tree_util.tree_map(lambda _: P(), example_params),)
        mapped = jax.shard_map(
            step_local,
            mesh=self.mesh,
            in_specs=in_specs,
            out_specs=tree_spec,
            check_vma=False,
        )
        return jax.jit(mapped)
