"""Process-wide counters and spans of the trace schedule engine.

A leaf module: it imports nothing of ``repro``, so the kernels that write
these counters (:mod:`repro.kernels.segment_reduce`) load without the
analytical core.  :mod:`repro.core.trace` writes them too and publishes a
snapshot as ``trace_cache_info()["stats"]``; the benchmark reads them
there.

The counters are observability, not behaviour: how many edge-list sorts,
schedule computations, schedule-cache hits and builder invocations
actually happened.  The §15 tuner's cache-reuse regression gates on
``factorizations`` — a multi-capacity tune must never silently re-sort
the edge list per candidate.  Beside them, the span totals of the
schedule engine (:class:`_span`): host nanoseconds summed over calls
(over threads too, so they can exceed wall time), and the bytes the
device engine uploads.  All values are integers.
"""

from __future__ import annotations

import sys
import threading
import time

__all__ = ["reset_trace_stats", "trace_stats"]

_TRACE_STATS = {
    "factorizations": 0,     # actual sorts (not disk-cache rehydrations)
    "schedule_computes": 0,  # per-capacity O(U) boundary-flag passes
    "schedule_cache_hits": 0,  # per-trace LRU hits
    "schedule_disk_hits": 0,   # on-disk schedule_cache hits
    "trace_builds": 0,       # dataset builder invocations (cold resolves)
    "schedules_ns": 0,       # GraphTrace.schedule(s), any engine, lock wait
    "schedule_upload_ns": 0,     # jax engine: operands to the device
    "schedule_upload_bytes": 0,  # jax engine: nbytes of those operands
    "schedule_factorization_uploads": 0,  # jax engine: pair factorization
    "schedule_dispatch_ns": 0,   # jax engine: enqueueing the program
    "schedule_wait_ns": 0,       # jax engine: device compute + read-back
    "schedule_hist_mxu": 0,      # jax engine: capacities on the MXU kernel
    "schedule_hist_scatter": 0,  # jax engine: capacities on scatter-adds
}

#: Guards ``_TRACE_STATS`` read-modify-write cycles.  The serve engine
#: (DESIGN.md §18) hammers the counters from many request threads; an
#: unguarded ``+=`` loses increments under the GIL's bytecode-boundary
#: preemption.
_STATS_LOCK = threading.Lock()


def _bump_stat(name: str, n: int = 1) -> None:
    with _STATS_LOCK:
        _TRACE_STATS[name] += n


class _span:
    """Context manager: add the block's host nanoseconds to the integer
    ``_TRACE_STATS[counter]`` and mark it as ``name`` on the profiler's
    host timeline (a ``jax.profiler.TraceAnnotation``, which records only
    while a profiler session runs), so device gaps line up with it.

    jax is only looked up, never imported: a process that has not
    imported it runs no profiler session, and the numpy engine path
    stays free of it."""

    __slots__ = ("_counter", "_annotation", "_t0")

    def __init__(self, name: str, counter: str) -> None:
        self._counter = counter
        jax = sys.modules.get("jax")
        self._annotation = (None if jax is None
                            else jax.profiler.TraceAnnotation(name))

    def __enter__(self) -> None:
        if self._annotation is not None:
            self._annotation.__enter__()
        self._t0 = time.perf_counter_ns()

    def __exit__(self, *exc) -> None:
        _bump_stat(self._counter, time.perf_counter_ns() - self._t0)
        if self._annotation is not None:
            self._annotation.__exit__(*exc)


def trace_stats() -> dict:
    """A consistent snapshot of every counter."""
    with _STATS_LOCK:
        return dict(_TRACE_STATS)


def reset_trace_stats() -> None:
    """Zero the process-wide trace work counters (see trace_cache_info)."""
    with _STATS_LOCK:
        for key in _TRACE_STATS:
            _TRACE_STATS[key] = 0
