"""The GatedGCN forward in float64 on the host, over the edge list: the
plain reference that decides ``correct`` in ``pattern.infer``. It imports
nothing of the program; it reads the parameters as nested numpy arrays
under the names the configuration's model gives them.

Layer (Bresson & Laurent, arXiv:1711.07553, as configured by Dwivedi et
al., arXiv:2003.00982), i the receiver and j the sender of edge ij, every
Linear with a bias, BN in eval mode with running statistics:

    ê_ij  = C e_ij + D h_i + E h_j
    η_ij  = σ(ê_ij) / (Σ_j' σ(ê_ij') + 1e-6)
    h'_i  = h_i + ReLU(BN_h(A h_i + Σ_j η_ij ⊙ B h_j))
    e'_ij = e_ij + ReLU(BN_e(ê_ij))

Input: an embedding of the integer node labels; e⁰ = Linear(1, d) of
ones. Readout: an MLP with ReLU between its layers.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

__all__ = ["gatedgcn_forward"]

BN_EPS = 1e-5
GATE_EPS = 1e-6


def _bn(x: np.ndarray, p: dict) -> np.ndarray:
    scale = p["gamma"] / np.sqrt(p["var"] + BN_EPS)
    x *= scale
    x += p["beta"] - p["mean"] * scale
    return x


def _segment_sum(x: np.ndarray, seg: np.ndarray, n: int) -> np.ndarray:
    """Rows of ``x`` summed by ``seg``, which is sorted."""
    out = np.zeros((n,) + x.shape[1:], x.dtype)
    if seg.size:
        heads = np.flatnonzero(np.r_[True, seg[1:] != seg[:-1]])
        out[seg[heads]] = np.add.reduceat(x, heads, axis=0)
    return out


def _forward(params: dict, labels, snd, rcv) -> np.ndarray:
    """One block-diagonal piece of the batch, edges sorted by receiver."""
    f64 = lambda x: np.asarray(x, np.float64)
    n = labels.size
    h = f64(params["embed_h"])[labels]
    e = np.broadcast_to(f64(params["embed_e"]).sum(axis=0)
                        + f64(params["embed_e_b"]), (snd.size, h.shape[1]))
    layers = params["layers"]
    for layer in range(f64(layers["A"]).shape[0]):
        lp = {k: (f64(v[layer]) if not isinstance(v, dict)
                  else {kk: f64(vv[layer]) for kk, vv in v.items()})
              for k, v in layers.items()}
        lin = lambda name, x: x @ lp[name] + lp["b" + name]
        e_hat = lin("D", h)[rcv]
        e_hat += lin("E", h)[snd]
        e_hat += lin("C", e)
        sig = np.negative(e_hat)
        np.exp(sig, out=sig)
        sig += 1.0
        np.reciprocal(sig, out=sig)
        den = _segment_sum(sig, rcv, n)
        sig *= lin("B", h)[snd]
        # Σ_j η_ij B h_j, with η's denominator taken out of the sum
        msgs = _segment_sum(sig, rcv, n) / (den + GATE_EPS)
        h = h + np.maximum(_bn(lin("A", h) + msgs, lp["bn_h"]), 0.0)
        e = e + np.maximum(_bn(e_hat, lp["bn_e"]), 0.0)
    mlp = params["mlp"]
    for i, p in enumerate(mlp):
        h = h @ f64(p["w"]) + f64(p["b"])
        if i < len(mlp) - 1:
            h = np.maximum(h, 0.0)
    return h


def gatedgcn_forward(params: dict, labels, senders, receivers,
                     graph_ids=None) -> np.ndarray:
    """Logits (N, n_classes) in float64.

    With ``graph_ids`` the batch is cut into pieces of whole graphs, which
    share no edge, and the pieces run on threads (numpy's array work runs
    outside the interpreter lock)."""
    labels = np.asarray(labels, np.int64)
    snd = np.asarray(senders, np.int64)
    rcv = np.asarray(receivers, np.int64)
    n = labels.size
    gid = (np.zeros(n, np.int64) if graph_ids is None
           else np.asarray(graph_ids, np.int64))
    n_pieces = max(1, min(os.cpu_count() or 1, 16, int(gid.max()) + 1))
    piece = gid * n_pieces // (int(gid.max()) + 1)

    def run(k):
        nodes = np.flatnonzero(piece == k)
        local = np.full(n, -1, np.int64)
        local[nodes] = np.arange(nodes.size)
        edges = np.flatnonzero(piece[rcv] == k)
        edges = edges[np.argsort(rcv[edges], kind="stable")]
        return nodes, _forward(params, labels[nodes], local[snd[edges]],
                               local[rcv[edges]])

    with ThreadPoolExecutor(n_pieces) as pool:
        parts = list(pool.map(run, range(n_pieces)))
    out = np.empty((n, parts[0][1].shape[1]))
    for nodes, logits in parts:
        out[nodes] = logits
    return out
