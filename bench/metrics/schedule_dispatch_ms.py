"""Milliseconds per capacity spent enqueueing the jitted schedule
program: the program's ``repro.trace.schedule.dispatch`` span."""

import program_stats


def read(view):
    return program_stats.ms_per_capacity(view, "schedule_dispatch_ns")
