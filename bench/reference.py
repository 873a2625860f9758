"""Plain references that decide ``correct``. They import nothing of the
program under test and read only the benchmark's own data.

* Tile schedule counts: for a balanced contiguous partition into
  ``n_tiles = ceil(V / capacity)`` tiles of stride ``K = ceil(V / n_tiles)``,
  the vertices and in-edges of each tile, its remote in-edges (source in
  another tile) and its halo (distinct remote sources).
* The GCN forward ``Â relu(Â X W1) W2`` in float64 on the host, with Â the
  symmetric-normalised adjacency with self-loops (Kipf & Welling, eq. 2).
"""

from __future__ import annotations

import numpy as np

__all__ = ["geometry", "tile_counts", "tile_counts_by_unique",
           "gcn_norm_edges", "gcn_forward"]

COUNT_FIELDS = ("vertex_counts", "edge_counts", "halo_counts",
                "remote_edge_counts")


def geometry(n_nodes: int, capacity: int) -> tuple[int, int]:
    n_tiles = -(-int(n_nodes) // int(capacity))
    return n_tiles, -(-int(n_nodes) // n_tiles)


def _vertex_counts(n_nodes: int, n_tiles: int, K: int) -> np.ndarray:
    bounds = np.minimum(np.arange(n_tiles + 1, dtype=np.int64) * K, n_nodes)
    return np.diff(bounds)


def tile_counts(snd: np.ndarray, rcv: np.ndarray, n_nodes: int,
                capacity: int) -> dict:
    """Counts for one capacity in O(E).

    Needs edges sorted by ``(sender, receiver)`` (as the generator returns
    them): the destination tile then never decreases within a sender's
    run, so each distinct ``(tile, source)`` pair starts where the sender
    or the tile changes.
    """
    n_tiles, K = geometry(n_nodes, capacity)
    Kd = snd.dtype.type(K)
    dst = rcv // Kd
    remote = (snd // Kd) != dst
    start = np.empty(dst.size, dtype=bool)
    if dst.size:
        start[0] = True
        np.not_equal(dst[1:], dst[:-1], out=start[1:])
        start[1:] |= snd[1:] != snd[:-1]
    start &= remote
    return {
        "n_tiles": n_tiles, "K": K,
        "vertex_counts": _vertex_counts(n_nodes, n_tiles, K),
        "edge_counts": np.bincount(dst, minlength=n_tiles),
        "halo_counts": np.bincount(dst[start], minlength=n_tiles),
        "remote_edge_counts": np.bincount(dst[remote], minlength=n_tiles),
    }


def tile_counts_by_unique(snd: np.ndarray, rcv: np.ndarray, n_nodes: int,
                          capacity: int) -> dict:
    """The same counts from ``np.unique`` over (tile, source) keys: any edge
    order, O(E log E). The tests hold :func:`tile_counts` to it."""
    n_tiles, K = geometry(n_nodes, capacity)
    snd = snd.astype(np.int64)
    dst = rcv.astype(np.int64) // K
    remote = snd // K != dst
    pairs = np.unique(dst[remote] * n_nodes + snd[remote])
    return {
        "n_tiles": n_tiles, "K": K,
        "vertex_counts": _vertex_counts(n_nodes, n_tiles, K),
        "edge_counts": np.bincount(dst, minlength=n_tiles),
        "halo_counts": np.bincount(pairs // n_nodes, minlength=n_tiles),
        "remote_edge_counts": np.bincount(dst[remote], minlength=n_tiles),
    }


def gcn_norm_edges(snd: np.ndarray, rcv: np.ndarray, n_nodes: int):
    """Edges of Â = D^-1/2 (A + I) D^-1/2 as (senders, receivers, coeff),
    sorted by receiver; coefficients in float64."""
    loops = np.arange(n_nodes, dtype=np.int64)
    s = np.concatenate([snd.astype(np.int64), loops])
    r = np.concatenate([rcv.astype(np.int64), loops])
    deg_in = np.bincount(r, minlength=n_nodes).astype(np.float64)
    deg_out = np.bincount(s, minlength=n_nodes).astype(np.float64)
    coeff = 1.0 / np.sqrt(deg_in[r] * deg_out[s])
    order = np.argsort(r, kind="stable")
    return s[order], r[order], coeff[order]


def _aggregate(m: np.ndarray, s, r, coeff, n_nodes: int) -> np.ndarray:
    starts = np.searchsorted(r, np.arange(n_nodes))
    return np.add.reduceat(m[s] * coeff[:, None], starts, axis=0)


def gcn_forward(x: np.ndarray, w1: np.ndarray, w2: np.ndarray,
                snd: np.ndarray, rcv: np.ndarray, n_nodes: int) -> np.ndarray:
    """``Â relu(Â X W1) W2`` in float64 over the first ``n_nodes`` rows."""
    s, r, c = gcn_norm_edges(snd, rcv, n_nodes)
    x = np.asarray(x[:n_nodes], dtype=np.float64)
    h = _aggregate(x @ np.asarray(w1, np.float64), s, r, c, n_nodes)
    np.maximum(h, 0.0, out=h)
    return _aggregate(h @ np.asarray(w2, np.float64), s, r, c, n_nodes)
