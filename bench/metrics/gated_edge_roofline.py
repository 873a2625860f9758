"""Share of the gated edge kernel's roofline: the least time the chip
needs for the edge layers' required work (``gated_work.
gatedgcn_edge_layer`` at the batch's unpadded edges and nodes, times the
layers, times the forwards) over the device time of the
``gated_edge_layer`` ops in the traced window."""

import gated_work
import traces
import work

KERNEL = "gated_edge_layer"


def read(view):
    if view.trace is None or "layout" not in view.record:
        return None
    kernel_s = traces.op_ns(view.trace, KERNEL) / 1e9
    if kernel_s <= 0.0:
        return None
    cfg = view.config
    need = gated_work.gatedgcn_edge_layer(view.record["edges"],
                                          view.record["nodes"],
                                          cfg["d_hidden"])
    least = (work.least_seconds(need, view.peak) * cfg["n_layers"]
             * view.record["forwards"])
    return 100.0 * least / kernel_s
