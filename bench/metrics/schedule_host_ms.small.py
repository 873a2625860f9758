"""Milliseconds per capacity of host work in ``GraphTrace.schedules``
outside its upload, dispatch and wait spans (the call's self time)."""

import program_stats


def read(view):
    return program_stats.host_ms_per_capacity(view)
