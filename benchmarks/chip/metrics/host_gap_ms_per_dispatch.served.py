"""Mean device-idle time between consecutive executions of the
ensemble-step program, in milliseconds: the host's share of each
dispatch."""


def read(r):
    gaps = r.trace.step_gap_idle_ns
    if not gaps:
        return None
    return sum(gaps) / len(gaps) / 1e6
