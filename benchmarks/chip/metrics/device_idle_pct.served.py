"""Share of the traced window in which no operation ran on the device, in
percent, averaged over the cell's chips."""


def read(r):
    share = r.trace.idle_share
    return None if share is None else 100.0 * share
