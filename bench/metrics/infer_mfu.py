"""The whole forward's share of the chip's peak FLOP/s: required FLOPs
(``work.gcn_forward``) times forwards, over the traced window's host-clock
seconds, over the peak."""

import work


def read(view):
    if "forwards" not in view.record:
        return None
    cfg = view.config
    need = work.gcn_forward(cfg["n_nodes"], view.record["nnz"],
                            (cfg["d_in"], cfg["d_hidden"], cfg["n_classes"]))
    rate = need.flops * view.record["forwards"] / view.record["elapsed_s"]
    return 100.0 * rate / view.peak["flops_per_s"]
