"""End-to-end driver: lid-driven cavity at Re=100, validated against Ghia
et al. (1982) — the paper's own demonstration application (its Fig. 3),
several hundred solver steps through the full framework stack, reached
through the ``repro.api`` front door: the scenario's ANALYSIS schedule
bin delivers the Ghia comparison as run diagnostics.

Run:  PYTHONPATH=src python examples/cavity_flow.py [--n 48] [--t-end 12]
"""
import argparse

import numpy as np

from repro.launch.compile_cache import use_compile_cache


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=48)
    ap.add_argument("--t-end", type=float, default=12.0)
    args = ap.parse_args()

    from repro import api
    from repro.cfd.cavity import GHIA_RE100_U

    print(f"lid-driven cavity Re=100, {args.n}^2 grid, t_end={args.t_end}")
    rt = api.runtime(n=args.n)
    res = rt.run("cavity", t_end=args.t_end, re=100.0, progress=200)
    errors = res.diagnostics["ghia"]
    print(f"steps: {res.steps_done}")
    print(f"Ghia centerline deviation: u_rms={errors['u_rms']:.4f} "
          f"v_rms={errors['v_rms']:.4f}")

    # ASCII profile: u(y) through the vertical centerline vs Ghia points
    y, u = res.diagnostics["centerline_u"]
    print("\n  u(y) at x=0.5   (*=ours, o=Ghia)")
    for gy, gu in GHIA_RE100_U[1:-1]:
        ui = float(np.interp(gy, y, u))
        col = int((ui + 0.4) / 1.4 * 58)
        gcol = int((gu + 0.4) / 1.4 * 58)
        line = [" "] * 60
        line[min(max(gcol, 0), 59)] = "o"
        line[min(max(col, 0), 59)] = "*"
        print(f"  y={gy:5.3f} |{''.join(line)}|")
    ok = errors["u_rms"] < 0.035 and errors["v_rms"] < 0.035
    print("\nVALIDATION", "PASSED" if ok else "FAILED")


if __name__ == "__main__":
    main()
