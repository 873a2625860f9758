"""Device engine for trace schedules: jitted JAX with an MXU histogram.

The amortized trace partitioner (DESIGN.md §13) reduces every
per-capacity schedule quantity to *segmented counts over one shared
sorted-edge factorization*: the unique ``(sender, receiver)`` pairs in
sender-major order plus their edge multiplicities.  ``dst_tile =
receiver // K`` is monotone within each sender segment, so the
deduplicated ``(dst_tile, source)`` pairs of any stride K are runs
delimited by a boundary flag, and the halo / cut-edge totals are
histograms of those flags (and multiplicity-weighted flags) over
destination tiles.  This module runs that pass on the accelerator; it is
the count function of ``engine="jax"`` in :mod:`repro.core.trace`, which
imports it lazily, and the numpy engine stays the default.

* :func:`upload_factorization` moves the factorization to the device;
  the caller keeps the device arrays for as many sweeps as it likes
  (``core/trace`` keeps one trace's resident across calls).
* :func:`schedule_counts` is the per-capacity entry: XLA computes the
  tile ids and the halo / cut-weight flags elementwise, then both
  histograms are built either by the two-level one-hot MXU kernel (tile
  ``t`` split as ``t >> 7`` and ``t & 127``: a ``(128, B)`` one-hot of
  the low part contracted with the high part's one-hot weighted by the
  halo flag and by each byte plane of the cut weight, all exact in bf16,
  float32 within an edge block and int32 across blocks) or, above
  ``MXU_HIST_MAX_TILES`` tiles, where that kernel's work outgrows it, by
  ``jax.ops.segment_sum`` scatter-adds.  Both are bit-identical integers
  to the numpy engine, pinned in tests.  The tile axis is padded to a
  static ``n_tiles_pad`` so a whole capacity sweep shares ONE
  compilation.

The uploads and the dispatch are timed as the
``repro.trace.schedule.upload`` / ``.dispatch`` spans, and the bytes
uploaded, the factorization transfers and the histogram path taken are
counted, in the engine counters of :mod:`repro.trace_stats`.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.trace_stats import _bump_stat, _span

from . import resolve_interpret

__all__ = [
    "upload_factorization",
    "schedule_counts",
    "boundary_flags",
]

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

    One transfer serves every capacity passed with the result to
    :func:`schedule_counts`, for as long as the caller keeps it.  Timed
    as the ``repro.trace.schedule.upload`` span; the bytes are counted in
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
