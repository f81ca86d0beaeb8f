"""The benchmark's own tests: run them explicitly,

    JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests

They run each driver, metric reader, the control and the faults at tiny
sizes on the CPU, reduce a recorded chip trace, and compile each cell's
step for a described TPU v5e.
"""
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
for p in (HERE, HERE.parents[1] / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def held_back_cells(monkeypatch):
    import tiny

    tiny.patch_harness(monkeypatch.setattr)
