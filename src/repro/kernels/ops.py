"""Jitted public wrappers around the Pallas kernels.

``interpret=None`` (the default) is resolved by
:func:`repro.kernels.resolve_interpret`: compiled for the chip on a TPU
backend, Pallas interpret mode elsewhere.  ``flash_attention`` takes the
model-layout (B, S, H, D) tensors and handles the (B*H, S, D) flattening +
GQA head replication so :mod:`repro.models.attention` can swap it in
one-for-one.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from .edge_aggregate import fused_aggregate_combine
from .edge_aggregate_unfused import aggregate_pass, combine_pass
from .embedding_bag import embedding_bag as _embedding_bag
from .flash_attention import flash_attention_bhsd


@partial(jax.jit, static_argnames=("block_n", "block_k", "interpret"))
def gnn_aggregate_combine(adjacency: jax.Array, x: jax.Array, w: jax.Array,
                          *, block_n: int = 256, block_k: int = 256,
                          interpret: Optional[bool] = None) -> jax.Array:
    return fused_aggregate_combine(adjacency, x, w, block_n=block_n,
                                   block_k=block_k, interpret=interpret)


@partial(jax.jit, static_argnames=("block_n", "block_k", "interpret"))
def gnn_aggregate(adjacency: jax.Array, x: jax.Array, *,
                  block_n: int = 256, block_k: int = 256,
                  interpret: Optional[bool] = None) -> jax.Array:
    """Unfused pass 1: Y_agg = A @ X (the aggregate materializes in HBM)."""
    return aggregate_pass(adjacency, x, block_n=block_n, block_k=block_k,
                          interpret=interpret)


@partial(jax.jit, static_argnames=("block_n", "interpret"))
def gnn_combine(y_agg: jax.Array, w: jax.Array, *, block_n: int = 256,
                interpret: Optional[bool] = None) -> jax.Array:
    """Unfused pass 2: Y = Y_agg @ W (reads the inter-phase buffer back).

    Jitted separately from :func:`gnn_aggregate` on purpose — the pair is
    the HyGCN inter-phase analogue, and fusing the passes into one program
    would let XLA elide exactly the traffic being modelled."""
    return combine_pass(y_agg, w, block_n=block_n, interpret=interpret)


@partial(jax.jit, static_argnames=("causal", "window", "softcap",
                                   "block_q", "block_k", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: Optional[int] = None,
                    softcap: Optional[float] = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: Optional[bool] = None) -> jax.Array:
    """q (B, S, H, D); k, v (B, S, Hk, D) with Hk | H (GQA)."""
    b, s, h, d = q.shape
    hk = k.shape[2]
    rep = h // hk
    if rep > 1:
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    to_bhsd = lambda t: t.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    o = flash_attention_bhsd(to_bhsd(q), to_bhsd(k), to_bhsd(v),
                             causal=causal, window=window, softcap=softcap,
                             block_q=min(block_q, s), block_k=min(block_k, s),
                             interpret=interpret)
    return o.reshape(b, h, s, d).transpose(0, 2, 1, 3)


@partial(jax.jit, static_argnames=("interpret",))
def embedding_bag(table: jax.Array, indices: jax.Array, *,
                  interpret: Optional[bool] = None) -> jax.Array:
    return _embedding_bag(table, indices, interpret=interpret)
