"""Work counts of what a cell asks for, independent of how the program does it.

Rooflines divide the least time the chip could take for the *required*
work by measured device time, so these counts never charge an
implementation's padding, dense blocks or recomputation.
"""

from __future__ import annotations

from dataclasses import dataclass

__all__ = ["Work", "gcn_layer", "gcn_forward", "schedule_sweep_bytes",
           "least_seconds", "F32", "PAIR_BYTES"]

F32 = 4
#: One unique (sender, receiver) pair of the factorization: two int32 ids
#: and an int32 multiplicity (the new-sender flag can be derived).
PAIR_BYTES = 12
#: One nonzero of Â as a (row, column, value) triple of 4-byte words.
TRIPLE_BYTES = 12


@dataclass(frozen=True)
class Work:
    flops: float
    bytes: float

    def __add__(self, other: "Work") -> "Work":
        return Work(self.flops + other.flops, self.bytes + other.bytes)


def gcn_layer(n_nodes: int, nnz: int, f_in: int, f_out: int) -> Work:
    """One GCN layer ``Â H W`` at the unpadded node count.

    FLOPs: the cheaper association, ``(Â H) W`` or ``Â (H W)``, counting a
    multiply-add as 2. Bytes: read H once, W once and Â's nonzeros as
    12-byte triples, and write the output once, all float32.
    """
    agg_first = 2.0 * nnz * f_in + 2.0 * n_nodes * f_in * f_out
    combine_first = 2.0 * n_nodes * f_in * f_out + 2.0 * nnz * f_out
    flops = min(agg_first, combine_first)
    nbytes = (F32 * (n_nodes * f_in + f_in * f_out + n_nodes * f_out)
              + TRIPLE_BYTES * nnz)
    return Work(flops, float(nbytes))


def gcn_forward(n_nodes: int, nnz: int, widths) -> Work:
    """A whole forward: one :func:`gcn_layer` per consecutive width pair."""
    total = Work(0.0, 0.0)
    for f_in, f_out in zip(widths[:-1], widths[1:]):
        total = total + gcn_layer(n_nodes, nnz, f_in, f_out)
    return total


def schedule_sweep_bytes(unique_pairs: int) -> float:
    """One read of the factorization for a whole capacity sweep."""
    return float(PAIR_BYTES * unique_pairs)


def least_seconds(work: Work, peak: dict) -> float:
    """The larger of compute time and memory time at the chip's peaks."""
    return max(work.flops / peak["flops_per_s"],
               work.bytes / peak["hbm_bytes_per_s"])
