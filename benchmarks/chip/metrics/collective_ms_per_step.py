"""Device time of the collective-permute operations (the halo exchange),
per device step, in milliseconds, averaged over the chips."""


def read(r):
    steps = r.record.get("device_steps")
    if not r.trace.collective_ns or not steps:
        return None
    return r.trace.collective_ns / steps / 1e6
