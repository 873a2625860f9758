"""Share of the traced window in which no operation ran on the device."""

import traces


def read(view):
    return traces.idle_percent(view.trace)
