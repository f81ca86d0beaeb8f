# Make `tests.helpers` importable regardless of invocation directory, and
# keep the main session at exactly 1 CPU device (multi-device behaviour is
# exercised in subprocesses; the 512-device dry-run sets XLA_FLAGS itself).
import os
import signal
import sys

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)
sys.path.insert(0, os.path.join(_ROOT, "src"))  # bare `pytest` without PYTHONPATH


@pytest.fixture(autouse=True)
def _multidevice_per_test_timeout(request):
    """Per-test wall-clock limit for the ``multidevice`` lane.

    Each multidevice test spawns a fresh interpreter that compiles for a
    forced device mesh; a wedged subprocess would otherwise eat the whole
    job-level timeout and mask which test hung.  CI sets
    ``REPRO_TEST_TIMEOUT`` (seconds) for the multidevice lane; unset (or
    on non-POSIX hosts) this is a no-op.  SIGALRM interrupts the blocking
    ``subprocess.run`` wait, so the alarm fires even mid-subprocess.
    """
    limit = int(os.environ.get("REPRO_TEST_TIMEOUT", "0"))
    if (limit <= 0 or not hasattr(signal, "SIGALRM")
            or request.node.get_closest_marker("multidevice") is None):
        yield
        return

    def _expired(signum, frame):
        raise TimeoutError(
            f"multidevice test exceeded REPRO_TEST_TIMEOUT={limit}s")

    old = signal.signal(signal.SIGALRM, _expired)
    signal.alarm(limit)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)
