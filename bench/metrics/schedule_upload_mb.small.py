"""Megabytes uploaded to the device per capacity: the program's
``schedule_upload_bytes`` counter (four O(U) operands and the stride)."""

import program_stats


def read(view):
    nbytes = program_stats.per_capacity(view, "schedule_upload_bytes")
    return None if nbytes is None else nbytes / 1e6
