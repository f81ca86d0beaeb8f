"""Device busy time inside the step program's executions, per device step
advanced in the traced window, in milliseconds (a farm's device step
advances every slot)."""


def read(r):
    steps = r.record.get("device_steps")
    if r.trace.step_busy_ns is None or not r.trace.n_devices or not steps:
        return None
    return r.trace.step_busy_ns / steps / 1e6
