"""EmbeddingBag Pallas kernel: scalar-prefetched row gather + pooled sum.

JAX has no native EmbeddingBag; the jnp path is take + sum (ref.py).  On
TPU the gather is the hot path of DLRM, so here the bag indices are
*scalar-prefetched* — the BlockSpec index_map reads the index array to pick
which (1, D) table row block the DMA engine fetches next, turning the
random-access gather into a software-pipelined stream of row copies (the
TPU answer to the paper's ``loadvert`` streaming constraint min(B, M*sigma)).

Grid: (batch, bag); each inner step accumulates one row into the output
block (revisited across the bag dimension).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import resolve_interpret


def _kernel(idx_ref, table_ref, out_ref):
    h = pl.program_id(1)

    @pl.when(h == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += table_ref[...].astype(out_ref.dtype)


def embedding_bag(table: jax.Array, indices: jax.Array, *,
                  interpret: Optional[bool] = None) -> jax.Array:
    """table (V, D) f32, indices (B, hot) int32 -> (B, D) summed bags."""
    v, d = table.shape
    b, hot = indices.shape

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, hot),
        in_specs=[
            pl.BlockSpec((1, d), lambda bi, h, idx_ref: (idx_ref[bi, h], 0)),
        ],
        out_specs=pl.BlockSpec((1, d), lambda bi, h, idx_ref: (bi, 0)),
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, d), table.dtype),
        interpret=resolve_interpret(interpret),
    )(indices, table)
