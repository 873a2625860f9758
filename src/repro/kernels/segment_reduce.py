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

* :func:`schedule_counts` — the jitted path: XLA computes the tile ids
  and the halo / cut-weight flags elementwise, then both histograms are
  built either by the two-level one-hot MXU kernel (tile ``t`` split as
  ``t >> 7`` and ``t & 127``: a ``(128, B)`` one-hot of the low part
  contracted with the high part's one-hot weighted by the halo flag and
  by each byte plane of the cut weight, all exact in bf16, float32 within
  an edge block and int32 across blocks) or, above
  ``MXU_HIST_MAX_TILES`` tiles, where that kernel's work outgrows it, by
  ``jax.ops.segment_sum`` scatter-adds.  Both are bit-identical integers
  to the numpy engine, pinned in tests.  The tile axis is padded to a
  static ``n_tiles_pad`` so a whole capacity sweep shares ONE
  compilation (``GraphTrace.schedules(caps, engine="jax")`` passes the
  sweep's max tile count), and :func:`upload_factorization` moves the
  factorization to the device once for all of them.  The uploads and the
  dispatch are timed as the ``repro.trace.schedule.upload`` /
  ``.dispatch`` spans, the bytes uploaded, the factorization transfers
  and the histogram path taken counted, in :mod:`repro.core.trace`'s
  counters.
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
from repro.core.trace import _bump_stat, _span

from . import resolve_interpret

__all__ = [
    "upload_factorization",
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
#: Edges per grid step of the MXU histogram kernel: a block's byte-plane
#: sums stay below 255 * 32768 < 2^24, exact in float32.
_MXU_BLOCK_EDGES = 32768
#: Edges per MXU contraction inside a block (2048 ran 17% faster than
#: 1024 on a TPU v5e, PERF.md).
_CHUNK_EDGES = 2048
_LANES = 128
#: Padded tile counts up to this take the MXU histogram kernel, larger
#: ones the scatter-adds.  On a TPU v5e at 123.7 M pairs the kernel beat
#: the scatters 8x even here, the largest count measured (PERF.md).
MXU_HIST_MAX_TILES = 62_500


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def boundary_flags(new_src: jax.Array, tile: jax.Array) -> jax.Array:
    """True where a new ``(source, dst_tile)`` run starts in the unique
    sender-major pair list (``new_src`` is the precomputed new-sender
    mask; the first entry always starts a run)."""
    if tile.shape[0] == 0:
        return jnp.zeros((0,), dtype=bool)
    head = jnp.ones((1,), dtype=bool)
    return new_src | jnp.concatenate([head, tile[1:] != tile[:-1]])


def _scatter_histograms(tile, halo, cut, n_tiles_pad: int):
    """The two histograms as XLA scatter-adds (int32 segment sums)."""
    return (jax.ops.segment_sum(halo, tile, num_segments=n_tiles_pad),
            jax.ops.segment_sum(cut, tile, num_segments=n_tiles_pad))


def _mxu_hist_kernel(ids_ref, halo_ref, cut_ref, out_ref, *, h_pad, planes):
    """One edge block into the resident ``(128, (1+planes)*h_pad)`` int32
    histogram: tile ``t`` lands at row ``t & 127``, column ``r*h_pad +
    (t >> 7)`` of plane ``r`` (0 the halo flag, ``1+j`` byte ``j`` of the
    cut weight)."""
    @pl.when(pl.program_id(0) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    sub = _CHUNK_EDGES // _LANES            # sublane rows per chunk

    def row_iota(n):
        return jax.lax.broadcasted_iota(jnp.int32, (n, _CHUNK_EDGES), 0)

    def lanes(x):
        # (sub, 128) edges -> (1, CHUNK): one row of edges on the lanes.
        return jnp.concatenate([x[k:k + 1, :] for k in range(sub)], axis=1)

    def chunk(c, acc):
        r = pl.multiple_of(c * sub, sub)
        ids = lanes(ids_ref[pl.ds(r, sub), :])
        halo = lanes(halo_ref[pl.ds(r, sub), :]).astype(jnp.float32)
        cut = lanes(cut_ref[pl.ds(r, sub), :])
        onehot_lo = (row_iota(_LANES) == (ids & (_LANES - 1))
                     ).astype(jnp.bfloat16)
        onehot_hi = row_iota(h_pad) == (ids >> 7)
        weights = [halo] + [((cut >> (8 * j)) & 255).astype(jnp.float32)
                            for j in range(planes)]
        rhs = jnp.concatenate([jnp.where(onehot_hi, w, 0.0)
                               for w in weights]).astype(jnp.bfloat16)
        # (128, C) x ((1+planes)*h_pad, C) over C on the MXU: 0/1 and byte
        # operands are exact in bf16, and a block's sums (< 255 * block
        # < 2^24) are exact in its float32 accumulation.
        return acc + jax.lax.dot_general(
            onehot_lo, rhs, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)

    acc = jax.lax.fori_loop(
        0, ids_ref.shape[0] // sub, chunk,
        jnp.zeros(out_ref.shape, jnp.float32))
    out_ref[...] += acc.astype(jnp.int32)


def _mxu_histograms(tile, halo, cut, max_weight, n_tiles_pad: int, *,
                    interpret: Optional[bool] = None):
    """The two histograms on the MXU (two-level one-hot, byte planes)."""
    u = tile.shape[0]
    u_pad = _round_up(max(u, 1), _MXU_BLOCK_EDGES)

    def rows(x):
        return jnp.pad(x.astype(jnp.int32), (0, u_pad - u)
                       ).reshape(-1, _LANES)

    operands = (rows(tile), rows(halo), rows(cut))
    h_pad = _round_up(-(-n_tiles_pad // _LANES), 8)
    in_spec = pl.BlockSpec((_MXU_BLOCK_EDGES // _LANES, _LANES),
                           lambda i: (i, 0))

    def planes_branch(planes):
        def run(ids, halo2, cut2):
            shape = (_LANES, (1 + planes) * h_pad)
            acc = pl.pallas_call(
                functools.partial(_mxu_hist_kernel, h_pad=h_pad,
                                  planes=planes),
                grid=(u_pad // _MXU_BLOCK_EDGES,),
                in_specs=[in_spec] * 3,
                out_specs=pl.BlockSpec(shape, lambda i: (0, 0)),
                out_shape=jax.ShapeDtypeStruct(shape, jnp.int32),
                interpret=resolve_interpret(interpret),
            )(ids, halo2, cut2)
            # Plane r, hi-major: tile hi * 128 + lo.
            hist = [acc[:, r * h_pad:(r + 1) * h_pad].T.reshape(-1)
                    [:n_tiles_pad] for r in range(1 + planes)]
            cut_hist = sum(h << (8 * j) for j, h in enumerate(hist[1:]))
            return hist[0], cut_hist
        return run

    # One byte plane while every weight fits a byte (a simple graph's are
    # 0/1), else all four: chosen on the device, no host round trip.
    return jax.lax.cond(max_weight < 256, planes_branch(1),
                        planes_branch(4), *operands)


@functools.partial(jax.jit, static_argnames=("n_tiles_pad", "interpret"))
def _schedule_counts_jnp(u_snd, u_rcv, u_new_src, mult, K, n_tiles_pad,
                         interpret=None):
    tile = u_rcv // K
    remote = (u_snd // K) != tile
    new_pair = boundary_flags(u_new_src, tile)
    halo = (new_pair & remote).astype(jnp.int32)
    cut = jnp.where(remote, mult, 0)
    if n_tiles_pad <= MXU_HIST_MAX_TILES:
        return _mxu_histograms(tile, halo, cut, jnp.max(mult, initial=0),
                               n_tiles_pad, interpret=interpret)
    return _scatter_histograms(tile, halo, cut, n_tiles_pad)


def upload_factorization(u_snd, u_rcv, u_new_src, mult):
    """The pair factorization as device arrays, multiplicities as int32.

    One transfer serves every capacity of a sweep: pass the result to
    :func:`schedule_counts` for each.  Timed as the
    ``repro.trace.schedule.upload`` span; the bytes are counted in
    ``schedule_upload_bytes`` and the transfer itself in
    ``schedule_factorization_uploads``.
    """
    with _span("repro.trace.schedule.upload", "schedule_upload_ns"):
        fact = (jnp.asarray(u_snd), jnp.asarray(u_rcv),
                jnp.asarray(u_new_src), jnp.asarray(mult, jnp.int32))
    _bump_stat("schedule_upload_bytes", sum(a.nbytes for a in fact))
    _bump_stat("schedule_factorization_uploads")
    return fact


def schedule_counts(u_snd, u_rcv, u_new_src, mult, K, n_tiles_pad: int):
    """(halo_counts, remote_edge_counts) over a padded tile axis, jitted.

    Operands are the shared factorization of ``GraphTrace``: unique
    ``(sender, receiver)`` pairs in sender-major order, the new-sender
    mask, and the per-pair edge multiplicities, as host arrays or as the
    device arrays of :func:`upload_factorization` (then only ``K`` moves
    from the host).  ``K`` is the (dynamic) tile stride, ``n_tiles_pad``
    the static padded tile count — tiles beyond ``ceil(V/K)`` come back
    0, and a whole capacity sweep padded to its max tile count shares one
    compilation.  Integer-exact (int32
    totals; counts are bounded by E).  Up to ``MXU_HIST_MAX_TILES`` tiles
    the histograms run on the MXU kernel, above it as scatter-adds; the
    path taken is counted in ``schedule_hist_mxu`` /
    ``schedule_hist_scatter``.
    """
    n_tiles_pad = int(n_tiles_pad)
    fact = (u_snd, u_rcv, u_new_src, mult)
    if not all(isinstance(x, jax.Array) for x in fact):
        fact = upload_factorization(*fact)
    with _span("repro.trace.schedule.upload", "schedule_upload_ns"):
        K = jnp.asarray(K, fact[1].dtype)
    _bump_stat("schedule_upload_bytes", K.nbytes)
    _bump_stat("schedule_hist_mxu" if n_tiles_pad <= MXU_HIST_MAX_TILES
               else "schedule_hist_scatter")
    with _span("repro.trace.schedule.dispatch", "schedule_dispatch_ns"):
        return _schedule_counts_jnp(*fact, K, n_tiles_pad)


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
