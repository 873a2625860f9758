"""SBM_PATTERN batches (Dwivedi et al., arXiv:2003.00982, section and
appendix on the SBM datasets), made from a seed.

Each graph is a stochastic block model of ``communities`` communities
whose sizes are drawn uniformly from ``community_size`` (both ends
included), with edge probability ``p`` inside a community and ``q``
between two; a pattern of ``pattern_nodes`` nodes is appended, its nodes
joined to each other with probability ``p_pattern`` and to each node of
the SBM graph with probability ``q_pattern``. Node labels are uniform in
``{0, ..., node_vocab - 1}``. Graphs are simple and symmetric: each
undirected pair once, stored in both directions, no self-loop.

A batch concatenates ``n_graphs`` graphs, nodes graph after graph; edges
come back sorted by (sender, receiver) within each graph.
"""

from __future__ import annotations

import numpy as np

__all__ = ["pattern_batch"]


def _graph(rng: np.random.Generator, cfg: dict):
    sizes = rng.integers(cfg["community_size"][0],
                         cfg["community_size"][1] + 1, cfg["communities"])
    comm = np.repeat(np.arange(sizes.size), sizes)
    n_sbm = comm.size
    n = n_sbm + cfg["pattern_nodes"]
    prob = np.where(comm[:, None] == comm[None, :], cfg["p"], cfg["q"])
    full = np.full((n, n), cfg["q_pattern"])
    full[:n_sbm, :n_sbm] = prob
    full[n_sbm:, n_sbm:] = cfg["p_pattern"]
    upper = np.triu(rng.random((n, n)) < full, 1)
    snd, rcv = np.nonzero(upper | upper.T)
    labels = rng.integers(0, cfg["node_vocab"], n)
    return n, snd, rcv, labels


def pattern_batch(cfg: dict):
    """(senders, receivers, graph_ids, labels), int32, of the batch that
    ``cfg["graph_seed"]`` fixes; raises if its totals differ from the
    configuration's ``n_nodes``, ``n_edges`` and ``n_undirected_edges``."""
    rng = np.random.default_rng(
        np.random.SeedSequence([int(cfg["graph_seed"]), 0x9A77]))
    snd, rcv, gid, labels = [], [], [], []
    offset = 0
    for g in range(cfg["n_graphs"]):
        n, s, r, lab = _graph(rng, cfg)
        snd.append(s + offset)
        rcv.append(r + offset)
        gid.append(np.full(n, g))
        labels.append(lab)
        offset += n
    out = tuple(np.concatenate(x).astype(np.int32)
                for x in (snd, rcv, gid, labels))
    made = {"n_nodes": offset, "n_edges": int(out[0].size),
            "n_undirected_edges": int(out[0].size) // 2}
    for key, value in made.items():
        if key in cfg and cfg[key] != value:
            raise RuntimeError(f"generator made {key} = {value}, the "
                               f"configuration states {cfg[key]}")
    return out
