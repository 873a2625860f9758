"""Segment-reduce engine for trace schedules: jitted JAX + a Pallas kernel.

The amortized trace partitioner (DESIGN.md §13) reduces every
per-capacity schedule quantity to *segmented counts over one shared
sorted-edge factorization*: the unique ``(sender, receiver)`` pairs in
sender-major order plus their edge multiplicities.  ``dst_tile =
receiver // K`` is monotone within each sender segment, so the
deduplicated ``(dst_tile, source)`` pairs of any stride K are runs
delimited by a boundary flag, and the halo / cut-edge totals are
histograms of those flags (and multiplicity-weighted flags) over
destination tiles.

This module is the accelerator-resident version of that pass:

* :func:`schedule_counts` — the jitted jnp path
  (``jax.ops.segment_sum`` over int32 flags; bit-identical integers to
  the numpy engine, pinned in tests).  The tile axis is padded to a
  static ``n_tiles_pad`` so a whole capacity sweep shares ONE
  compilation (``GraphTrace.schedules(caps, engine="jax")`` passes the
  sweep's max tile count).
* :func:`tile_histogram` — the Pallas segment-reduce kernel: grid over
  edge blocks, each block one-hot-expands its tile ids against a
  broadcasted iota into a ``(n_tiles, block)`` matrix and contracts it
  with the block's weights on the MXU into a VMEM-resident
  ``(1, n_tiles)`` output (the same masked-matmul trick the block-dense
  SpMM kernels use — the MXU eats the zeros).  Interpreted on the CPU,
  compiled on a TPU (:func:`repro.kernels.resolve_interpret`); float32
  accumulation at full contraction precision is exact for integer counts
  below 2^24 per tile (asserted by the wrapper), and a one-hot block
  larger than the chip's VMEM is refused.
* :func:`schedule_counts_pallas` — the halo/multiplicity counts routed
  through the Pallas kernel, numpy-parity-pinned in the test battery.

Like every kernel in this package, the module is an *optional* fast
path: `repro.core.trace` imports it lazily, and the numpy engine remains
the default and the semantic reference.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.tpu_model import TPU_V5E

from . import resolve_interpret

__all__ = [
    "schedule_counts",
    "schedule_counts_pallas",
    "tile_histogram",
    "padded_histogram",
    "boundary_flags",
]

#: Edges per Pallas grid step (one-hot block height).
DEFAULT_BLOCK_EDGES = 4096
#: float32 accumulation holds integers exactly below this.
_F32_EXACT = 1 << 24
#: Largest one-hot block (bytes) the kernel may build: the chip's VMEM.
_ONEHOT_LIMIT_BYTES = TPU_V5E.vmem_bytes


def boundary_flags(new_src: jax.Array, tile: jax.Array) -> jax.Array:
    """True where a new ``(source, dst_tile)`` run starts in the unique
    sender-major pair list (``new_src`` is the precomputed new-sender
    mask; the first entry always starts a run)."""
    if tile.shape[0] == 0:
        return jnp.zeros((0,), dtype=bool)
    head = jnp.ones((1,), dtype=bool)
    return new_src | jnp.concatenate([head, tile[1:] != tile[:-1]])


@functools.partial(jax.jit, static_argnums=(5,))
def _schedule_counts_jnp(u_snd, u_rcv, u_new_src, mult, K, n_tiles_pad):
    tile = u_rcv // K
    remote = (u_snd // K) != tile
    new_pair = boundary_flags(u_new_src, tile)
    halo = jax.ops.segment_sum((new_pair & remote).astype(jnp.int32),
                               tile, num_segments=n_tiles_pad)
    cut = jax.ops.segment_sum(jnp.where(remote, mult, 0),
                              tile, num_segments=n_tiles_pad)
    return halo, cut


def schedule_counts(u_snd, u_rcv, u_new_src, mult, K, n_tiles_pad: int):
    """(halo_counts, remote_edge_counts) over a padded tile axis, jitted.

    Operands are the shared factorization of ``GraphTrace``: unique
    ``(sender, receiver)`` pairs in sender-major order, the new-sender
    mask, and the per-pair edge multiplicities.  ``K`` is the (dynamic)
    tile stride, ``n_tiles_pad`` the static padded tile count — tiles
    beyond ``ceil(V/K)`` come back 0, and a whole capacity sweep padded
    to its max tile count shares one compilation.  Integer-exact (int32
    segment sums; counts are bounded by E).
    """
    u_snd = jnp.asarray(u_snd)
    u_rcv = jnp.asarray(u_rcv)
    return _schedule_counts_jnp(u_snd, u_rcv, jnp.asarray(u_new_src),
                                jnp.asarray(mult, jnp.int32),
                                jnp.asarray(K, u_rcv.dtype),
                                int(n_tiles_pad))


# ---------------------------------------------------------------------------
# The Pallas kernel: blocked one-hot histogram (segment-reduce by matmul).
# ---------------------------------------------------------------------------
def _hist_kernel(ids_ref, w_ref, out_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    ids = ids_ref[...]                       # (1, B) int32 tile ids
    w = w_ref[...]                           # (1, B) float32 weights
    block, n = ids.shape[1], out_ref.shape[1]
    # One-hot expansion against a broadcasted iota, tiles on the rows:
    # column e selects the destination-tile row of edge e (padding ids
    # select nothing).  Comparing against the (1, B) ids broadcasts them
    # down the rows, so no gather is lowered.
    onehot = (jax.lax.broadcasted_iota(jnp.int32, (n, block), 0) == ids
              ).astype(jnp.float32)
    # (1, B) x (n, B) contracted over B: the block's histogram in one MXU
    # pass, at full f32 precision so integer counts stay exact.
    out_ref[...] += jax.lax.dot_general(
        w, onehot, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def tile_histogram(ids, weights, n_tiles: int, *,
                   block_edges: int = DEFAULT_BLOCK_EDGES,
                   interpret: Optional[bool] = None) -> jax.Array:
    """``bincount(ids, weights, minlength=n_tiles)`` as a Pallas kernel.

    ``ids`` int tile ids in ``[0, n_tiles)``, ``weights`` non-negative
    integer-valued counts (float32-able); both 1-D of equal length.
    Accumulates in float32 — exact for integer totals below 2^24, so the
    guard bounds the *accumulated weight* (total count), which also
    bounds every per-tile total and every individual weight.  Raises
    ``ValueError`` where the ``(n_tiles, block_edges)`` one-hot block
    would exceed the chip's VMEM; there is no fallback.
    """
    ids = jnp.asarray(ids, dtype=jnp.int32)
    weights = jnp.asarray(weights, dtype=jnp.float32)
    if ids.ndim != 1 or ids.shape != weights.shape:
        raise ValueError(f"ids/weights must be equal-length 1-D arrays, got "
                         f"{ids.shape} and {weights.shape}")
    # float64 on the host: the guard itself must not round.
    total = float(np.asarray(weights, dtype=np.float64).sum())
    if total >= _F32_EXACT:
        raise ValueError(
            f"tile_histogram accumulates in float32 (integer-exact below "
            f"2^24 per tile); a total weight of {total:.4g} can overflow "
            "that — use the jitted segment_sum path (schedule_counts) at "
            "this scale")
    n_tiles = int(n_tiles)
    block = int(block_edges)
    e_pad = _round_up(max(int(ids.shape[0]), 1), block)
    n_pad = _round_up(max(n_tiles, 1), 128)
    onehot_bytes = n_pad * block * 4
    if onehot_bytes > _ONEHOT_LIMIT_BYTES:
        raise ValueError(
            f"tile_histogram builds a ({n_pad} x {block}) float32 one-hot "
            f"block per grid step ({onehot_bytes / 2**20:.0f} MiB), more "
            f"than the chip's {_ONEHOT_LIMIT_BYTES / 2**20:.0f} MiB of VMEM; "
            "use fewer tiles (a larger capacity), smaller block_edges, or "
            "the jitted segment_sum path (schedule_counts)")
    # Pad ids with n_pad (matches no iota row) and weights with 0.
    ids2 = jnp.full((1, e_pad), n_pad, dtype=jnp.int32)
    ids2 = ids2.at[0, :ids.shape[0]].set(ids)
    w2 = jnp.zeros((1, e_pad), dtype=jnp.float32)
    w2 = w2.at[0, :weights.shape[0]].set(weights)
    out = padded_histogram(ids2, w2, n_pad=n_pad, block_edges=block,
                           interpret=interpret)
    return out[0, :n_tiles]


def padded_histogram(ids2, w2, *, n_pad: int, block_edges: int,
                     interpret: Optional[bool] = None) -> jax.Array:
    """The bare ``pallas_call`` of :func:`tile_histogram`: ``(1, E_pad)``
    int32 ids and float32 weights, ``E_pad`` a multiple of
    ``block_edges``, into a ``(1, n_pad)`` float32 histogram.  No guards;
    shapes only, so it lowers from abstract operands."""
    e_pad = ids2.shape[1]
    return pl.pallas_call(
        _hist_kernel,
        grid=(e_pad // block_edges,),
        in_specs=[pl.BlockSpec((1, block_edges), lambda i: (0, i)),
                  pl.BlockSpec((1, block_edges), lambda i: (0, i))],
        out_specs=pl.BlockSpec((1, n_pad), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, n_pad), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(ids2, w2)


def schedule_counts_pallas(u_snd, u_rcv, u_new_src, mult, K, n_tiles: int, *,
                           block_edges: int = DEFAULT_BLOCK_EDGES,
                           interpret: Optional[bool] = None):
    """(halo_counts, remote_edge_counts) with the histograms on the
    Pallas kernel (float32; numpy-parity-pinned on CI sizes)."""
    u_snd = jnp.asarray(u_snd)
    u_rcv = jnp.asarray(u_rcv)
    K = jnp.asarray(K, u_rcv.dtype)
    tile = (u_rcv // K).astype(jnp.int32)
    remote = (u_snd // K).astype(jnp.int32) != tile
    new_pair = boundary_flags(jnp.asarray(u_new_src), tile)
    halo = tile_histogram(tile, (new_pair & remote).astype(jnp.float32),
                          n_tiles, block_edges=block_edges,
                          interpret=interpret)
    cut = tile_histogram(tile,
                         jnp.where(remote, jnp.asarray(mult, jnp.float32),
                                   0.0),
                         n_tiles, block_edges=block_edges,
                         interpret=interpret)
    return halo, cut
