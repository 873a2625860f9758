"""Profiler traces: recording a window, and reducing it to numbers.

A trace is reduced in two steps. :func:`extract` turns the profiler's
``.xplane.pb`` into a plain, JSON-able record: for each TPU device plane
the events of its ``XLA Ops`` and ``XLA Modules`` lines, and the host
spans whose names start with ``bench.`` (the ``jax.profiler.
TraceAnnotation`` spans the drivers put around each call into a layer)
or ``repro.`` (the program's own spans inside those calls).
Everything after that works on the plain record, so the tests can feed
it a small recorded trace:

* :func:`busy_ns`: the union of the device-op intervals inside the window,
  averaged over devices;
* :func:`idle_percent`: the rest of the window, in percent;
* :func:`op_ns`: device time of ops by stable name
  (``%gnn_aggregate_combine.2 = ...`` is ``gnn_aggregate_combine``);
* :func:`idle_gaps`: the device's idle gaps inside the window, each named
  after the innermost kept host span that covers its middle;
* :func:`top_ops`: device time by ``program:op`` (``jit_fwd(123)`` is
  the program ``fwd``).

Host and device timestamps share one clock in these traces to within
about a millisecond, which is what the gap attribution can resolve.
"""

from __future__ import annotations

import bisect
import contextlib
import glob
import os
import re
import shutil
from typing import Iterable, Optional

__all__ = ["recording", "extract", "load", "stable_op_name",
           "stable_module_name", "union_ns", "window_of", "busy_ns",
           "idle_percent", "op_ns", "idle_gaps", "top_ops"]

WINDOW_SPAN = "bench.window"
#: Host spans kept by :func:`extract`: the drivers' and the program's.
SPAN_PREFIXES = ("bench.", "repro.")
_SUFFIX = re.compile(r"\.\d+$")


@contextlib.contextmanager
def recording(trace_dir: str):
    """Profile the body into ``trace_dir`` (emptied first). Host spans at
    the default level, no Python function tracer (it would swamp the
    drivers' loops)."""
    import jax

    shutil.rmtree(trace_dir, ignore_errors=True)
    os.makedirs(trace_dir, exist_ok=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def stable_op_name(raw: str) -> str:
    head = raw.split(" = ", 1)[0].strip().lstrip("%")
    return _SUFFIX.sub("", head)


def stable_module_name(raw: str) -> str:
    head = raw.split("(", 1)[0].strip()
    return head[4:] if head.startswith("jit_") else head


def extract(profile) -> dict:
    """Plain record of a ``jax.profiler.ProfileData``: see module doc."""
    devices, spans = {}, []
    for plane in profile.planes:
        if plane.name.startswith("/device:TPU:"):
            rec = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(
                    line.name)
                if key:
                    rec[key] = [[e.name, int(e.start_ns), int(e.duration_ns)]
                                for e in line.events]
            devices[plane.name] = rec
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                spans.extend([e.name, int(e.start_ns), int(e.duration_ns)]
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIXES))
    spans.sort(key=lambda s: s[1])
    return {"devices": devices, "spans": spans}


def load(trace_dir: str) -> Optional[dict]:
    """The plain record of the newest trace under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        return None
    return extract(ProfileData.from_file(files[-1]))


def union_ns(intervals: Iterable[tuple[int, int]]) -> int:
    """Length of the union of ``(start, end)`` intervals."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def window_of(rec: dict) -> tuple[int, int]:
    """(start, end) of the ``bench.window`` span."""
    for name, start, dur in rec["spans"]:
        if name == WINDOW_SPAN:
            return start, start + dur
    raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")


def _clipped(events, lo: int, hi: int) -> list[tuple[int, int]]:
    out = []
    for _, start, dur in events:
        s, e = max(start, lo), min(start + dur, hi)
        if e > s:
            out.append((s, e))
    return out


def busy_ns(rec: dict) -> float:
    """Device-busy nanoseconds inside the window, averaged over devices."""
    lo, hi = window_of(rec)
    devs = list(rec["devices"].values())
    if not devs:
        return 0.0
    return sum(union_ns(_clipped(d["ops"], lo, hi)) for d in devs) / len(devs)


def idle_percent(rec: Optional[dict]) -> Optional[float]:
    """Share of the window in which no op ran on the device, in percent;
    None without a trace or a device plane."""
    if rec is None or not rec["devices"]:
        return None
    lo, hi = window_of(rec)
    return 100.0 * (1.0 - busy_ns(rec) / (hi - lo))


def op_ns(rec: dict, name: str) -> float:
    """Device time of the ops with this stable name inside the window,
    averaged over devices."""
    lo, hi = window_of(rec)
    devs = list(rec["devices"].values())
    if not devs:
        return 0.0
    total = 0
    for d in devs:
        total += sum(e - s for s, e in _clipped(
            [ev for ev in d["ops"] if stable_op_name(ev[0]) == name],
            lo, hi))
    return total / len(devs)


def idle_gaps(rec: dict, limit: int = 10) -> list[list]:
    """The longest idle gaps of the first device inside the window, as
    ``[span name, seconds]``, each named after the innermost kept host
    span (``bench.*`` or ``repro.*``) covering its middle."""
    lo, hi = window_of(rec)
    devs = list(rec["devices"].values())
    busy = sorted(_clipped(devs[0]["ops"], lo, hi)) if devs else []
    gaps, cursor = [], lo
    for s, e in busy:
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, e)
    if hi > cursor:
        gaps.append((cursor, hi))
    spans = [(start, start + dur, name) for name, start, dur in rec["spans"]
             if name != WINDOW_SPAN]
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:limit]:
        mid = (s + e) / 2
        inner = [sp for sp in spans if sp[0] <= mid <= sp[1]]
        name = (min(inner, key=lambda sp: sp[1] - sp[0])[2] if inner
                else "outside bench spans")
        out.append([name, (e - s) / 1e9])
    return out


def top_ops(rec: dict, limit: int = 10) -> list[list]:
    """Device seconds by ``program:op`` on the first device, largest first."""
    lo, hi = window_of(rec)
    devs = list(rec["devices"].values())
    if not devs:
        return []
    mods = sorted((start, start + dur, stable_module_name(name))
                  for name, start, dur in devs[0]["modules"])
    starts = [m[0] for m in mods]
    totals: dict[str, int] = {}
    for name, start, dur in devs[0]["ops"]:
        s, e = max(start, lo), min(start + dur, hi)
        if e <= s:
            continue
        i = bisect.bisect_right(starts, start) - 1
        prog = mods[i][2] if i >= 0 and mods[i][1] >= start else "?"
        key = f"{prog}:{stable_op_name(name)}"
        totals[key] = totals.get(key, 0) + (e - s)
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:limit]
    return [[k, v / 1e9] for k, v in ranked]
