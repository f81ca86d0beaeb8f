"""Quickstart: the runtime front door in three lines.

Scenarios are registered problem declarations (config builder + parameter
schema + IC/analysis routines wired into the INITIAL/EVOLVE/ANALYSIS
schedule bins); the Runtime resolves them onto an execution stack — serial
driver here, simulation farm / decomposed mesh with the same three lines
plus a ``mesh_shape``.  Nothing below names a kernel, a halo exchange, or
a device: that is the point.

    rt = api.runtime(n=24)
    res = rt.run("cavity", t_end=2.0, re=100.0)
    print(res.diagnostics["ghia"])

Run:  PYTHONPATH=src python examples/quickstart.py
"""
from repro import api
from repro.launch.compile_cache import use_compile_cache


def main():
    use_compile_cache()
    # the registry: every problem the runtime can serve by name
    print("registered scenarios:")
    for name in api.scenario_names():
        print(f"  {name:18s} {api.get_scenario(name).description}")

    # -- the three-line quickstart -------------------------------------------
    rt = api.runtime(n=24)
    res = rt.run("cavity", t_end=2.0, re=100.0)
    print(f"\ncavity Re=100, {res.steps_done} steps "
          f"(terminated: {res.terminated})")
    print("Ghia centerline deviation:",
          {k: round(v, 4) for k, v in res.diagnostics["ghia"].items()})

    # same front door, different scenario + per-run parameters
    tg = rt.run("taylor_green", steps=40, nu=0.05)
    err = tg.diagnostics["analytic_error"]
    print(f"taylor_green nu=0.05: max |v - analytic| = "
          f"{max(err['err_vx'], err['err_vy']):.2e} at t={err['t']:.3f}")
    assert max(err["err_vx"], err["err_vy"]) < 5e-3
    assert res.steps_done > 0
    print("OK — scenario registry -> runtime -> driver stack, one surface.")


if __name__ == "__main__":
    main()
