"""Milliseconds per capacity the host waits for the device's counts and
reads them back: the program's ``repro.trace.schedule.wait`` span."""

import program_stats


def read(view):
    return program_stats.ms_per_capacity(view, "schedule_wait_ns")
