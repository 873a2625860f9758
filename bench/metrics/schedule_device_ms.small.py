"""Device-busy milliseconds per capacity schedule: the union of device-op
time inside the traced window over the capacities computed in it."""

import traces


def read(view):
    if view.trace is None or not view.record.get("capacities"):
        return None
    busy = traces.busy_ns(view.trace)
    if busy <= 0.0:
        return None
    return busy / 1e6 / view.record["capacities"]
