"""The benchmark's graph generator: simple symmetric graphs at published sizes.

Both configurations are undirected citation or co-purchase graphs stored
as directed edge lists with each undirected edge in both directions. The
generator reproduces that: ``n_undirected`` distinct unordered pairs
``{u, v}`` with ``u != v``, each emitted as ``u -> v`` and ``v -> u``, so
the result has exactly ``2 * n_undirected`` directed edges, no duplicate
pair and no self-loop.

Degrees are heavy-tailed (a Chung-Lu model): each endpoint of a candidate
pair is drawn with probability proportional to ``rank ** -exponent``, with
the ranks shuffled over the node ids, so hubs sit anywhere in the id
space. Candidates are drawn in rounds until enough distinct pairs exist,
and a seeded sample of the surplus is dropped. The same ``graph_seed``
gives the same pairs.

The edges come back sorted by ``(sender, receiver)`` as int32 arrays.
"""

from __future__ import annotations

import numpy as np

__all__ = ["simple_symmetric_graph", "in_row_ptr"]


def _endpoints(rng: np.random.Generator, perm: np.ndarray, exponent: float,
               size: int) -> np.ndarray:
    """Node ids drawn with P(rank r) ~ r**-exponent, by inverting the
    continuous power-law CDF over ranks [1, n + 1)."""
    n = perm.size
    q = 1.0 - exponent
    top = (n + 1.0) ** q - 1.0
    u = rng.random(size)
    u *= top
    u += 1.0
    np.power(u, 1.0 / q, out=u)
    ranks = u.astype(np.int64)
    ranks -= 1
    np.clip(ranks, 0, n - 1, out=ranks)
    return perm[ranks]


def simple_symmetric_graph(n_nodes: int, n_undirected: int, exponent: float,
                           seed: int) -> tuple[np.ndarray, np.ndarray]:
    """(senders, receivers), int32, sorted sender-major; see module doc."""
    n = int(n_nodes)
    m = int(n_undirected)
    if not 0.0 < exponent < 1.0:
        raise ValueError(f"exponent must lie in (0, 1), got {exponent}")
    if n < 2 or m < 1 or m > n * (n - 1) // 2:
        raise ValueError(f"cannot place {m} distinct pairs on {n} nodes")
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 0x5E1]))
    perm = rng.permutation(n).astype(np.int64)
    keys = np.empty(0, dtype=np.int64)
    while keys.size < m:
        draw = int((m - keys.size) * 1.05) + 1024
        a = _endpoints(rng, perm, exponent, draw)
        b = _endpoints(rng, perm, exponent, draw)
        lo = np.minimum(a, b)
        hi = np.maximum(a, b, out=b)
        del a
        keep = lo != hi
        lo = lo[keep]
        lo *= n
        lo += hi[keep]
        del hi, keep
        keys = np.union1d(keys, lo)
        del lo
    if keys.size > m:
        drop = rng.choice(keys.size, keys.size - m, replace=False)
        keys = np.delete(keys, drop)
    lo = keys // n
    hi = keys - lo * n
    del keys
    directed = np.empty(2 * m, dtype=np.int64)
    np.multiply(lo, n, out=directed[:m])
    directed[:m] += hi
    np.multiply(hi, n, out=directed[m:])
    directed[m:] += lo
    del lo, hi
    directed.sort()
    snd = (directed // n).astype(np.int32)
    directed %= n
    rcv = directed.astype(np.int32)
    return snd, rcv


def in_row_ptr(receivers: np.ndarray, n_nodes: int) -> np.ndarray:
    """int64 CSR row pointer by receiver (length ``n_nodes + 1``)."""
    ptr = np.zeros(int(n_nodes) + 1, dtype=np.int64)
    np.cumsum(np.bincount(receivers, minlength=int(n_nodes)), out=ptr[1:])
    return ptr
