"""The least time one chip could take for a convection step, over the
step's measured device time, in percent: ``step_roofline`` with the step's
own count, temperature included (``work_convection.py``)."""
import work_convection


def read(r):
    steps = r.record.get("device_steps")
    if r.trace.step_busy_ns is None or not r.trace.n_devices or not steps:
        return None
    measured_s = r.trace.step_busy_ns / steps / 1e9
    if measured_s <= 0:
        return None
    cells = r.record["cells_per_device_step"] // r.cell.chips
    least_s, _ = work_convection.least_step_seconds(
        cells, r.cell.config["jacobi_iters"], r.cell.devices[0].device_kind)
    return 100.0 * least_s / measured_s
