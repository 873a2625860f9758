"""Device time of the fused gated edge kernel a forward: the
``gated_edge_layer`` ops of the traced window (one a layer, inside the
layers' ``lax.scan``), over the forwards done. With ``infer_ms`` it
splits the edge kernel from the node side and the host."""

import traces

KERNEL = "gated_edge_layer"


def read(view):
    if view.trace is None or "layout" not in view.record:
        return None
    kernel_ns = traces.op_ns(view.trace, KERNEL)
    if kernel_ns <= 0.0:
        return None
    return kernel_ns / 1e6 / view.record["forwards"]
