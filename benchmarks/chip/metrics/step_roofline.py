"""The least time one chip could take for a step, over the step's measured
device time, in percent.  The least time is the larger of the step's
compulsory bytes over the chip's bandwidth and its FLOPs over its peak
(``work.py``), for the cells each chip holds."""
import work


def read(r):
    steps = r.record.get("device_steps")
    if r.trace.step_busy_ns is None or not r.trace.n_devices or not steps:
        return None
    measured_s = r.trace.step_busy_ns / steps / 1e9
    if measured_s <= 0:
        return None
    cells = r.record["cells_per_device_step"] // r.cell.chips
    least_s, _ = work.least_step_seconds(
        cells, r.cell.config["jacobi_iters"], r.cell.devices[0].device_kind)
    return 100.0 * least_s / measured_s
