"""Device steps advanced in the traced window over the executions of the
ensemble-step program in it: how many steps one dispatch carries."""


def read(r):
    runs = r.trace.step_executions
    steps = r.record.get("device_steps")
    if not runs or not steps:
        return None
    return steps / runs
