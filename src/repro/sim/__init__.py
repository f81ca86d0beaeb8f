"""Simulation farm: continuous-batching ensemble runtime for CFD workloads.

The serving pattern of :mod:`repro.serve.engine` (fixed device slots +
continuous batching) applied to stencil simulations: many independent
parameter variants of one case resident on a slot axis, advanced by a single
jitted vmapped step, with host-side admission/reclamation and a compile
cache so new work of an already-seen shape never recompiles.

    ensemble.py   the device layer — slot-stacked state, one step for all,
                  and the rules that place it on a mesh
    farm.py       the scheduler — queue, slots, termination, compile cache
    slots.py      the admission table — fixed slots + FIFO queue
    service.py    the front-end — submit/poll/result + evict/readmit
    scenarios.py  the registry — declarative problem specs (repro.api)

New code should reach this subsystem through :mod:`repro.api` (the
runtime front door); the constructors below remain public for one release
as the migration shim.
"""
from repro.sim.ensemble import EnsembleExecutor, stack_trees
from repro.sim.farm import (
    SimRequest, SimResult, SimulationFarm, compile_cache_stats,
    reset_compile_cache,
)
from repro.sim.scenarios import (
    ParamSpec, Scenario, UnknownScenarioError, get_scenario,
    register_scenario, scenario_names, unregister_scenario,
)
from repro.sim.service import SimulationService

__all__ = [
    "EnsembleExecutor", "ParamSpec", "Scenario", "SimRequest", "SimResult",
    "SimulationFarm", "SimulationService", "UnknownScenarioError",
    "compile_cache_stats", "get_scenario", "register_scenario",
    "reset_compile_cache", "scenario_names", "stack_trees",
    "unregister_scenario",
]
