"""Share of the unfused kernel pair's roofline: the least time the chip
needs for the forward's required work (``work.gcn_forward``, as for the
fused kernel) over the device time of the ``gnn_aggregate`` and
``gnn_combine`` kernel events, per forward."""

import traces
import work

KERNELS = ("gnn_aggregate", "gnn_combine")


def read(view):
    if view.trace is None or "forwards" not in view.record:
        return None
    kernel_s = sum(traces.op_ns(view.trace, k) for k in KERNELS) / 1e9
    if kernel_s <= 0.0:
        return None
    cfg = view.config
    need = work.gcn_forward(cfg["n_nodes"], view.record["nnz"],
                            (cfg["d_in"], cfg["d_hidden"], cfg["n_classes"]))
    least = work.least_seconds(need, view.peak) * view.record["forwards"]
    return 100.0 * least / kernel_s
