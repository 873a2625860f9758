"""Fused GNN aggregate+combine Pallas kernel (TPU-adapted from the paper).

The paper's two accelerators split a GNN layer into an *aggregation* stage
and a *combination* stage.  HyGCN pipelines them through an inter-phase
buffer whose write/read traffic (Table IV ``writeinterphase`` /
``readinterphase``) is, per Fig. 4, a dominant share of its off-chip data
movement.  EnGN avoids the buffer by running both stages on one PE array.

TPU adaptation (DESIGN.md §3):
* The gather/scatter aggregation becomes **block-dense SpMM**: the pipeline
  tiles the adjacency into (BN x BK) dense blocks (zeros where no edge —
  the MXU eats zeros at full rate, and real GNN accelerators for TPU-class
  hardware do exactly this), so aggregation is a masked matmul.
* Aggregate and combine are FUSED in one kernel: the aggregated tile lives
  in a VMEM accumulator and is immediately multiplied by the combine weight
  W — the inter-phase buffer collapses into registers.  The HBM traffic
  eliminated per (K-node, N-feature) tile is exactly the paper's
  ``K*N*sigma`` write + ``P_s*N*sigma`` read terms (the unfused two-pass
  baseline in :mod:`repro.kernels.edge_aggregate_unfused` pays them).

Grid: (num dst node blocks, num src node blocks).  For each dst block i the
kernel accumulates sum_j A[i,j] @ X[j] in VMEM and, on the last j, applies
the (F x T) combine weight and writes the (BN x T) output tile once.

Byte accounting (DESIGN.md §10): :func:`fused_grid_spec` is the single
source of the kernel's grid + block geometry — ``pallas_call`` consumes it
and :func:`fused_block_streams` re-exports the same index maps as
movement-level-named stream descriptors, so the conformance subsystem
(:mod:`repro.core.conformance`) measures the schedule the kernel actually
launches, not a transcription of it.

``interpret=None`` compiles for the chip on a TPU backend and interprets on
the CPU (:func:`repro.kernels.resolve_interpret`); ops.py wraps it jitted.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import resolve_interpret

DEFAULT_BLOCK_N = 256   # dst nodes per tile (the paper's K)
DEFAULT_BLOCK_K = 256   # src nodes per tile


def mxu_dot(a: jax.Array, b: jax.Array) -> jax.Array:
    """The aggregate/combine contraction, accumulated in f32.

    f32 operands contract at full f32 precision: Mosaic's default f32
    contraction misses the 1e-5 numerics bar on a v5e (2.2e-3 relative
    error at GCN-Cora layer 1, 2.6e-7 at full precision).  A bf16 operand
    keeps the default, the only contraction Mosaic accepts for it.
    """
    full = a.dtype == b.dtype == jnp.float32
    return jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST if full else None,
                   preferred_element_type=jnp.float32)


def _kernel(a_ref, x_ref, w_ref, out_ref, acc_ref, *, n_src_blocks: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Aggregation micro-step on the MXU: (BN, BK) @ (BK, F).
    acc_ref[...] += mxu_dot(a_ref[...], x_ref[...])

    @pl.when(j == n_src_blocks - 1)
    def _combine():
        # Combination stage fused in: no inter-phase buffer ever leaves VMEM.
        out_ref[...] = mxu_dot(acc_ref[...], w_ref[...]).astype(out_ref.dtype)


def fused_grid_spec(n: int, f: int, t: int, block_n: int, block_k: int):
    """Grid + (block_shape, index_map) geometry of the fused kernel.

    Returns ``(grid, in_geoms, out_geom)`` with one ``(shape, index_map)``
    pair per operand in call order (A, X, W) and one for the output.  The
    same pairs construct the ``pallas_call`` BlockSpecs and the conformance
    stream descriptors — keep them in sync by construction.
    """
    assert n % block_n == 0 and n % block_k == 0, (n, block_n, block_k)
    grid = (n // block_n, n // block_k)
    in_geoms = (
        ((block_n, block_k), lambda i, j: (i, j)),   # A tile
        ((block_k, f), lambda i, j: (j, 0)),         # X tile
        ((f, t), lambda i, j: (0, 0)),               # W (resident)
    )
    out_geom = ((block_n, t), lambda i, j: (i, 0))
    return grid, in_geoms, out_geom


def fused_block_streams(n: int, f: int, t: int, *,
                        block_n: int = DEFAULT_BLOCK_N,
                        block_k: int = DEFAULT_BLOCK_K,
                        elem_bytes: float = 4.0) -> dict:
    """Movement-level-named HBM stream descriptors of the fused kernel.

    Keys match the ``spmm_tiled`` dataflow's off-chip movement levels; each
    value carries the block shape, the *actual* kernel index map, the
    element width, and the transfer direction — everything the conformance
    schedule trace needs (DESIGN.md §10).
    """
    grid, (a_g, x_g, w_g), out_g = fused_grid_spec(n, f, t, block_n, block_k)
    return {
        "grid": grid,
        "streams": {
            "loadadjblocks": {"block_shape": a_g[0], "index_map": a_g[1],
                              "elem_bytes": elem_bytes, "kind": "read"},
            "loadvertblocks": {"block_shape": x_g[0], "index_map": x_g[1],
                               "elem_bytes": elem_bytes, "kind": "read"},
            "loadweights": {"block_shape": w_g[0], "index_map": w_g[1],
                            "elem_bytes": elem_bytes, "kind": "read"},
            "writeout": {"block_shape": out_g[0], "index_map": out_g[1],
                         "elem_bytes": elem_bytes, "kind": "write"},
        },
    }


def fused_aggregate_combine(adjacency: jax.Array, x: jax.Array, w: jax.Array,
                            *, block_n: int = DEFAULT_BLOCK_N,
                            block_k: int = DEFAULT_BLOCK_K,
                            interpret: Optional[bool] = None) -> jax.Array:
    """Y = (A @ X) @ W with A (N, N) block-dense, X (N, F), W (F, T).

    N must divide evenly into block_n/block_k tiles (the data pipeline pads
    graphs to these multiples, mirroring the paper's tiling preprocessing).
    """
    n, f = x.shape
    t = w.shape[1]
    assert adjacency.shape == (n, n), (adjacency.shape, n)
    assert w.shape[0] == f
    block_n = min(block_n, n)
    block_k = min(block_k, n)
    grid, in_geoms, out_geom = fused_grid_spec(n, f, t, block_n, block_k)

    return pl.pallas_call(
        functools.partial(_kernel, n_src_blocks=grid[1]),
        grid=grid,
        in_specs=[pl.BlockSpec(shape, imap) for shape, imap in in_geoms],
        out_specs=pl.BlockSpec(*out_geom),
        out_shape=jax.ShapeDtypeStruct((n, t), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_n, f), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(adjacency, x, w)
