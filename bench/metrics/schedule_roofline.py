"""Share of the schedule engine's roofline: one read of the factorization
per sweep (``work.schedule_sweep_bytes``) at peak HBM bandwidth, over the
device-busy time per sweep in the traced window."""

import traces
import work


def read(view):
    if view.trace is None or not view.record.get("sweeps"):
        return None
    busy_s = traces.busy_ns(view.trace) / 1e9
    if busy_s <= 0.0:
        return None
    least = (work.schedule_sweep_bytes(view.record["unique_pairs"])
             / view.peak["hbm_bytes_per_s"])
    return 100.0 * least * view.record["sweeps"] / busy_s
