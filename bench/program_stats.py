"""Per-capacity readings of the program's own span and counter totals.

`bench/drivers/sweep.py` keeps the window's change in every key of the
program's ``trace_cache_info()["stats"]`` under ``record["stats"]``. The
schedule engine adds there, per call, the host nanoseconds of its spans
(``schedules_ns`` for a whole ``GraphTrace.schedules`` call, and inside
it ``schedule_upload_ns``, ``schedule_dispatch_ns`` and
``schedule_wait_ns``) and the bytes it uploads
(``schedule_upload_bytes``). Each reading is such a total over the
capacities computed in the window; it is None where the program does not
keep the key, so a program without the spans reports nothing.
"""

INNER_SPANS = ("schedule_upload_ns", "schedule_dispatch_ns",
               "schedule_wait_ns")


def per_capacity(view, key: str):
    """``record["stats"][key]`` over the window's capacities, or None."""
    stats = view.record.get("stats") or {}
    n = view.record.get("capacities")
    if key not in stats or not n:
        return None
    return stats[key] / n


def ms_per_capacity(view, key: str):
    """A nanosecond total in milliseconds per capacity, or None."""
    ns = per_capacity(view, key)
    return None if ns is None else ns / 1e6


def host_ms_per_capacity(view):
    """Self time of the whole call: ``schedules_ns`` less its inner
    spans, in milliseconds per capacity, or None."""
    total = per_capacity(view, "schedules_ns")
    inner = [per_capacity(view, k) for k in INNER_SPANS]
    if total is None or None in inner:
        return None
    return (total - sum(inner)) / 1e6
