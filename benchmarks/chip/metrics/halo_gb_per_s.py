"""Halo bytes a device has to receive in a step (``halo_work.py``), over
the device time of the collective-permute operations a step
(``collective_ms_per_step``), in GB/s.  Nothing without a mesh."""
import halo_work


def read(r):
    steps = r.record.get("device_steps")
    if not r.trace.collective_ns or not steps or not r.cell.config.get("mesh"):
        return None
    return halo_work.step_bytes(r.cell.config) / (r.trace.collective_ns
                                                  / steps)
