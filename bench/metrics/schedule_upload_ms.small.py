"""Milliseconds per capacity spent handing the factorization to the
device: the program's ``repro.trace.schedule.upload`` span."""

import program_stats


def read(view):
    return program_stats.ms_per_capacity(view, "schedule_upload_ns")
