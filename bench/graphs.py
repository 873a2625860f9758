"""A configuration's graph: the benchmark's own copy, and the program's.

The graph stands in for the published dataset, so it is fixed per
configuration: made once per checkout from the configuration's
``graph_seed`` and kept as int32 ``.npy`` files under ``bench/.data``.
The references read this copy. The program receives the same edges the
way a user's graph enters it: a trace dataset registered with a cache
token, built with ``GraphTrace.from_factorization`` and kept by the
program's own trace store from then on.
"""

from __future__ import annotations

import os
import shutil
import tempfile
from pathlib import Path

import numpy as np

import graphgen

__all__ = ["graph_arrays", "register_trace", "dense_gcn_adjacency"]

#: Version of the generator's output; part of the program store's key.
GRAPH_TOKEN = "bench-simple-graph-1"


def _graph_dir(cfg: dict, data_dir: Path) -> Path:
    return Path(data_dir) / f"{cfg['name']}-g{cfg['graph_seed']}"


def graph_arrays(cfg: dict, data_dir: Path) -> tuple[np.ndarray, np.ndarray]:
    """(senders, receivers) of the configuration's graph, memory-mapped;
    generated and stored on first use."""
    path = _graph_dir(cfg, data_dir)
    if not (path / "rcv.npy").is_file():
        snd, rcv = graphgen.simple_symmetric_graph(
            cfg["n_nodes"], cfg["n_undirected_edges"],
            cfg["degree_exponent"], cfg["graph_seed"])
        if snd.size != cfg["n_edges"]:
            raise RuntimeError(f"generator made {snd.size} edges, the "
                               f"configuration states {cfg['n_edges']}")
        path.parent.mkdir(parents=True, exist_ok=True)
        tmp = Path(tempfile.mkdtemp(dir=path.parent, suffix=".tmp"))
        try:
            np.save(tmp / "snd.npy", snd)
            np.save(tmp / "rcv.npy", rcv)
            shutil.rmtree(path, ignore_errors=True)
            os.replace(tmp, path)
        finally:
            shutil.rmtree(tmp, ignore_errors=True)
    return (np.load(path / "snd.npy", mmap_mode="r"),
            np.load(path / "rcv.npy", mmap_mode="r"))


def register_trace(cfg: dict, data_dir: Path) -> tuple[str, dict]:
    """Register the graph as a program trace dataset; returns the
    ``(dataset, params)`` that resolve it."""
    from repro.core.trace import GraphTrace, register_trace_dataset

    def build(**_params) -> GraphTrace:
        snd, rcv = graph_arrays(cfg, data_dir)
        return GraphTrace.from_factorization(
            cfg["n_nodes"], snd, rcv,
            np.arange(snd.size + 1, dtype=np.int64),
            row_ptr=graphgen.in_row_ptr(rcv, cfg["n_nodes"]))

    name = f"bench.{cfg['name']}"
    register_trace_dataset(name, build, overwrite=True,
                           cache_token=GRAPH_TOKEN)
    params = {"graph_seed": cfg["graph_seed"], "n_nodes": cfg["n_nodes"],
              "n_edges": cfg["n_edges"],
              "degree_exponent": cfg["degree_exponent"]}
    return name, params


def dense_gcn_adjacency(snd, rcv, n_nodes: int, n_pad: int) -> np.ndarray:
    """Dense float32 Â = D^-1/2 (A + I) D^-1/2, zero-padded to n_pad."""
    loops = np.arange(n_nodes)
    s = np.concatenate([np.asarray(snd, np.int64), loops])
    r = np.concatenate([np.asarray(rcv, np.int64), loops])
    deg = np.bincount(r, minlength=n_nodes).astype(np.float64)
    out_deg = np.bincount(s, minlength=n_nodes).astype(np.float64)
    adj = np.zeros((n_pad, n_pad), dtype=np.float32)
    adj[r, s] = 1.0 / np.sqrt(deg[r] * out_deg[s])
    return adj
