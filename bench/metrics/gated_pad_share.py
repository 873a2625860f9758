"""Share of the edge layout's slots that are padding: the layout's own
counters, ``edge_pad_slots`` over ``edge_slots``
(``repro.kernels.gated_edge.layout_stats``), in percent. Every padded
slot is streamed by the kernel like an edge."""


def read(view):
    layout = view.record.get("layout") or {}
    if not layout.get("edge_slots"):
        return None
    return 100.0 * layout["edge_pad_slots"] / layout["edge_slots"]
