"""One run of ``tgv768-x4`` at a tiny size on four virtual CPU devices.

    python x4_case.py [fault]

Prints the run's ``correct`` and checks as one JSON line.  A separate
process, because the number of devices is fixed when JAX starts.
"""
import json
import os
import sys

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import conftest  # noqa: E402,F401  (puts the harness and src on the path)
import run  # noqa: E402
import tiny  # noqa: E402

tiny.patch_harness(setattr)
if len(sys.argv) > 1:
    import faults

    faults.plant(sys.argv[1], setattr)
out, _ = run.run_cell("tgv768-x4", tiny.SEED, 1.0, False, require_tpu=False,
                      overrides=tiny.overrides("tgv768-x4"))
print(json.dumps({"correct": out["correct"], "checks": out["checks"],
                  "metrics": out["metrics"]}))
