"""Faults planted in the program under test; the check must catch each.

``plant(name, setattr)`` breaks the timed path underneath the harness:
``setattr`` is pytest's ``monkeypatch.setattr`` or plain ``setattr``.

* ``unchanged``: the step returns its state as it got it.
* ``half_batch``: the farm's step advances only the first half of its slots.
* ``no_exchange``: the halo exchange between chips is left out; each block
  wraps onto itself instead.
* ``altered``: one value of the answer is changed where it is produced (the
  DNS step's output, the farm's harvested fields).
"""
import types

import jax
import jax.numpy as jnp


def plant(name: str, setattr) -> None:
    from repro.cfd import ns3d
    from repro.core import halo
    from repro.sim import ensemble, farm

    farm.reset_compile_cache()
    if name == "unchanged":
        setattr(ns3d.NavierStokes3D, "_step_local",
                lambda self, state, params=None: state)
    elif name == "altered":
        step = ns3d.NavierStokes3D._step_local
        read = ensemble.EnsembleExecutor.read_slot

        def altered_step(self, state, params=None):
            out = step(self, state, params)
            return dict(out, vx=out["vx"].at[0, 0, 0].add(0.05))

        def altered_read(self, slot):
            out = read(self, slot)
            out["vx"] = out["vx"].copy()
            out["vx"][0, 0, 0] += 0.05
            return out

        setattr(ns3d.NavierStokes3D, "_step_local", altered_step)
        setattr(ensemble.EnsembleExecutor, "read_slot", altered_read)
    elif name == "half_batch":
        make = farm.make_ensemble_step

        def half(solver, **kw):
            run_k = make(solver, **kw)

            def stepped(state, params, k):
                new = run_k(state, params, k)

                def keep(a, b):
                    n = a.shape[0]
                    mask = (jnp.arange(n) < n // 2).reshape(
                        (n,) + (1,) * (a.ndim - 1))
                    return jnp.where(mask, a, b)

                return jax.tree.map(keep, new, state)

            return stepped

        setattr(farm, "make_ensemble_step", half)
    elif name == "no_exchange":
        proxy = types.SimpleNamespace(**{
            k: getattr(jax.lax, k) for k in dir(jax.lax)
            if not k.startswith("__")})
        proxy.ppermute = lambda x, axis_name, perm: x
        setattr(halo, "lax", proxy)
    else:
        raise ValueError(f"unknown fault {name!r}")
