"""Work counts of GatedGCN's edge layer, independent of how the program
does it (see ``work.py``): never the layout's padding, lane padding or
the one-hot products that stand in for gathers."""

from __future__ import annotations

from work import F32, Work

__all__ = ["gatedgcn_edge_layer", "EDGE_ELEMENTWISE", "ID_BYTES"]

#: Elementwise operations per edge and channel: the two adds of ê, the
#: BN multiply-add, the ReLU and the residual add, σ (exp, add, divide),
#: the gated message, and the two receiver sums.
EDGE_ELEMENTWISE = 12
#: A sender and a receiver id per edge, int32.
ID_BYTES = 8


def gatedgcn_edge_layer(n_edges: int, n_nodes: int, d: int) -> Work:
    """One layer's edge half: ``ê = C e + D h_i + E h_j``, ``e'`` and the
    receiver sums of ``σ(ê) ⊙ B h_j`` and ``σ(ê)``.

    FLOPs: ``2 E d²`` for ``C e`` plus ``EDGE_ELEMENTWISE`` per edge and
    channel. Bytes, float32: read ``e`` and write ``e'`` once, read the
    three node projections and write the numerator and the denominator
    once, and read the edge ids."""
    flops = 2.0 * n_edges * d * d + EDGE_ELEMENTWISE * n_edges * d
    nbytes = (F32 * (2 * n_edges * d + 3 * n_nodes * d + 2 * n_nodes * d)
              + ID_BYTES * n_edges)
    return Work(flops, float(nbytes))
