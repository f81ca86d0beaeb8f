"""Test helpers: run snippets in a subprocess with a forced device count.

JAX locks the backend device count at first initialization, and the main
test session must see exactly 1 CPU device (smoke tests exercise the
single-device paths).  Multi-device behaviour (halo exchange over a real
mesh, sharded checkpointing, dry-runs) is therefore tested in subprocesses
with ``--xla_force_host_platform_device_count``.
"""
from __future__ import annotations

import ast
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_with_devices(script: str, n_devices: int = 8, timeout: int = 600):
    """Run ``script`` with ``n_devices`` fake host devices; return stdout."""
    env = dict(os.environ)
    # drop any inherited device-count flag (e.g. the CI multidevice lane's)
    # so the per-test count always wins
    inherited = [f for f in env.get("XLA_FLAGS", "").split()
                 if not f.startswith("--xla_force_host_platform_device_count")]
    env["XLA_FLAGS"] = " ".join(
        [f"--xla_force_host_platform_device_count={n_devices}"] + inherited)
    env["PYTHONPATH"] = os.path.join(REPO, "src") + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-c", script],
        env=env,
        capture_output=True,
        text=True,
        timeout=timeout,
    )
    if proc.returncode != 0:
        raise AssertionError(
            f"subprocess failed (rc={proc.returncode})\n"
            f"--- stdout ---\n{proc.stdout}\n--- stderr ---\n{proc.stderr}"
        )
    return proc.stdout


class MeshStub:
    """Just (axis_names, shape) — all the spec rules ever read.

    Lets one CPU assert the layout rules for any mesh geometry without
    forcing a device count.
    """

    def __init__(self, **axes):
        self.axis_names = tuple(axes)
        self.shape = dict(axes)


def imported_modules(path):
    """Every module a Python file imports, function-level imports
    included: ``import a.b`` yields ``a.b``; ``from a import b`` yields
    ``a`` and ``a.b``."""
    with open(path) as f:
        tree = ast.parse(f.read(), filename=path)
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                yield a.name
        elif isinstance(node, ast.ImportFrom) and node.module:
            yield node.module
            for a in node.names:      # "from repro.sim import farm"
                yield f"{node.module}.{a.name}"
