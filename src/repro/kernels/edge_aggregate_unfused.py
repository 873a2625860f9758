"""Unfused two-pass aggregate / combine Pallas kernels — HyGCN's analogue.

The counterpart of :mod:`repro.kernels.edge_aggregate`: the same block-dense
SpMM pipeline, but aggregation and combination run as two separately
compiled kernels with the aggregated (K x N) features materialized in HBM
between them — the TPU realization of HyGCN's inter-phase buffer (Table IV
``writeinterphase`` / ``readinterphase``).  Compiling the passes separately
is the point: the aggregate crosses the executable boundary, so its HBM
round-trip is measurable ground truth for the conformance subsystem
(:mod:`repro.core.conformance`), and the fused-minus-unfused measured delta
is exactly the paper's eliminated ``K*N*sigma + P_s*N*sigma`` terms.

Pass 1 — :func:`aggregate_pass`:  Y_agg = A @ X, grid (dst blocks, src
blocks), VMEM accumulator, aggregate tile written on the last src block.
Pass 2 — :func:`combine_pass`:    Y = Y_agg @ W, grid (dst blocks,).

Analytical counterpart: the registered ``spmm_unfused`` dataflow
(:mod:`repro.core.spmm_unfused`).  Like the fused kernel, each pass exposes
its grid + index-map geometry through a ``*_grid_spec`` /
``*_block_streams`` helper pair so conformance traces the launched
schedule, not a transcription (DESIGN.md §10).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import resolve_interpret
from .edge_aggregate import DEFAULT_BLOCK_K, DEFAULT_BLOCK_N, mxu_dot


def _aggregate_kernel(a_ref, x_ref, out_ref, acc_ref, *, n_src_blocks: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += mxu_dot(a_ref[...], x_ref[...])

    @pl.when(j == n_src_blocks - 1)
    def _flush():
        # The inter-phase spill HyGCN pays: the aggregate leaves the array.
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def _combine_kernel(y_ref, w_ref, out_ref):
    out_ref[...] = mxu_dot(y_ref[...], w_ref[...]).astype(out_ref.dtype)


def aggregate_grid_spec(n: int, f: int, block_n: int, block_k: int):
    """Grid + (block_shape, index_map) geometry of the aggregation pass."""
    assert n % block_n == 0 and n % block_k == 0, (n, block_n, block_k)
    grid = (n // block_n, n // block_k)
    in_geoms = (
        ((block_n, block_k), lambda i, j: (i, j)),   # A tile
        ((block_k, f), lambda i, j: (j, 0)),         # X tile
    )
    out_geom = ((block_n, f), lambda i, j: (i, 0))   # aggregate spill
    return grid, in_geoms, out_geom


def combine_grid_spec(n: int, f: int, t: int, block_n: int):
    """Grid + (block_shape, index_map) geometry of the combination pass."""
    assert n % block_n == 0, (n, block_n)
    grid = (n // block_n,)
    in_geoms = (
        ((block_n, f), lambda i: (i, 0)),            # aggregate re-fetch
        ((f, t), lambda i: (0, 0)),                  # W (resident)
    )
    out_geom = ((block_n, t), lambda i: (i, 0))
    return grid, in_geoms, out_geom


def aggregate_block_streams(n: int, f: int, *,
                            block_n: int = DEFAULT_BLOCK_N,
                            block_k: int = DEFAULT_BLOCK_K,
                            elem_bytes: float = 4.0) -> dict:
    """Movement-level-named stream descriptors of the aggregation pass,
    keyed to the ``spmm_unfused`` dataflow (DESIGN.md §10)."""
    grid, (a_g, x_g), out_g = aggregate_grid_spec(n, f, block_n, block_k)
    return {
        "grid": grid,
        "streams": {
            "loadadjblocks": {"block_shape": a_g[0], "index_map": a_g[1],
                              "elem_bytes": elem_bytes, "kind": "read"},
            "loadvertblocks": {"block_shape": x_g[0], "index_map": x_g[1],
                               "elem_bytes": elem_bytes, "kind": "read"},
            "writeinterphase": {"block_shape": out_g[0], "index_map": out_g[1],
                                "elem_bytes": elem_bytes, "kind": "write"},
        },
    }


def combine_block_streams(n: int, f: int, t: int, *,
                          block_n: int = DEFAULT_BLOCK_N,
                          elem_bytes: float = 4.0) -> dict:
    """Movement-level-named stream descriptors of the combination pass."""
    grid, (y_g, w_g), out_g = combine_grid_spec(n, f, t, block_n)
    return {
        "grid": grid,
        "streams": {
            "readinterphase": {"block_shape": y_g[0], "index_map": y_g[1],
                               "elem_bytes": elem_bytes, "kind": "read"},
            "loadweights": {"block_shape": w_g[0], "index_map": w_g[1],
                            "elem_bytes": elem_bytes, "kind": "read"},
            "writeout": {"block_shape": out_g[0], "index_map": out_g[1],
                         "elem_bytes": elem_bytes, "kind": "write"},
        },
    }


def aggregate_pass(adjacency: jax.Array, x: jax.Array, *,
                   block_n: int = DEFAULT_BLOCK_N,
                   block_k: int = DEFAULT_BLOCK_K,
                   interpret: Optional[bool] = None) -> jax.Array:
    """Y_agg = A @ X with A (N, N) block-dense, X (N, F)."""
    n, f = x.shape
    assert adjacency.shape == (n, n), (adjacency.shape, n)
    block_n = min(block_n, n)
    block_k = min(block_k, n)
    grid, in_geoms, out_geom = aggregate_grid_spec(n, f, block_n, block_k)

    return pl.pallas_call(
        functools.partial(_aggregate_kernel, n_src_blocks=grid[1]),
        grid=grid,
        in_specs=[pl.BlockSpec(shape, imap) for shape, imap in in_geoms],
        out_specs=pl.BlockSpec(*out_geom),
        out_shape=jax.ShapeDtypeStruct((n, f), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_n, f), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(adjacency, x)


def combine_pass(y_agg: jax.Array, w: jax.Array, *,
                 block_n: int = DEFAULT_BLOCK_N,
                 interpret: Optional[bool] = None) -> jax.Array:
    """Y = Y_agg @ W with Y_agg (N, F), W (F, T)."""
    n, f = y_agg.shape
    t = w.shape[1]
    assert w.shape[0] == f
    block_n = min(block_n, n)
    grid, in_geoms, out_geom = combine_grid_spec(n, f, t, block_n)

    return pl.pallas_call(
        _combine_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec(shape, imap) for shape, imap in in_geoms],
        out_specs=pl.BlockSpec(*out_geom),
        out_shape=jax.ShapeDtypeStruct((n, t), y_agg.dtype),
        interpret=resolve_interpret(interpret),
    )(y_agg, w)


def unfused_aggregate_combine(adjacency: jax.Array, x: jax.Array,
                              w: jax.Array, *,
                              block_n: int = DEFAULT_BLOCK_N,
                              block_k: int = DEFAULT_BLOCK_K,
                              interpret: Optional[bool] = None) -> jax.Array:
    """Two-pass Y = (A @ X) @ W — numerically the fused kernel's oracle
    twin; the aggregate round-trips through memory between the passes."""
    y_agg = aggregate_pass(adjacency, x, block_n=block_n, block_k=block_k,
                           interpret=interpret)
    return combine_pass(y_agg, w, block_n=block_n, interpret=interpret)
