"""Fused gated edge layer of GatedGCN over a receiver-tiled edge layout.

One GatedGCN layer (Bresson & Laurent, arXiv:1711.07553), with ``i`` the
receiver and ``j`` the sender of edge ``ij``:

    ê_ij  = C e_ij + D h_i + E h_j
    e'_ij = e_ij + ReLU(BN_e(ê_ij))
    num_i = Σ_j σ(ê_ij) ⊙ B h_j,    den_i = Σ_j σ(ê_ij)

This kernel computes the edge half: it streams the edge state ``e`` once
and writes ``e'`` once, and returns the per-receiver numerator and
denominator; the node half (``A h``, ``BN_h``, ReLU, residual) stays in
XLA.  ê, σ and the messages never leave VMEM.

Layout (:func:`build_layout`, on the host in one pass).  Node ids are
placed into *node tiles* of ``TILE_NODES`` = 128 slots, whole graphs at a
time: a graph that fits a tile never straddles two, a larger one starts on
a tile boundary and may span ``MAX_GRAPH_TILES`` tiles.  Edges are grouped
by (receiver tile, sender tile), receiver tile major, and each group is
padded to whole blocks of ``block_edges``, so every edge block gathers
from one receiver tile and one sender tile and every receiver tile's
blocks are consecutive.  A batch of small graphs is block-diagonal, so a
tile's senders are its own graph's nodes.

Kernel (grid over edge blocks; the two tiles per block are scalar
prefetch).  The edge state is kept transposed, ``(d_pad, slots)``: the
width (70 for the published model, padded to a multiple of 8 sublanes) on
sublanes and edges on lanes.  The gathers ``D h_i``, ``[E h_j | B h_j]``
and the receiver sums are one-hot products on the MXU, the idiom of
``segment_reduce._mxu_hist_kernel``: a 0/1 one-hot is exact in bf16, and
each float32 operand is split into three bf16 parts (``hi + mid + lo``,
24 mantissa bits) whose sums reconstruct it, so the gathers are exact and
the sums are float32 sums.  ``C e`` takes the six bf16 products that
float32 at ``Precision.HIGHEST`` takes (hi·hi, mid·hi, lo·hi, hi·mid,
mid·mid, hi·lo), stacked along one contraction of ``6 d_pad`` so that the
MXU pads it once rather than six times.

Node order.  The kernel's node operands and sums are in the layout's
node-slot order (``node_of_slot``); :func:`edge_layer` takes and returns
node rows in that order, so a model that keeps its node state in slot
order (``gatedgcn.forward`` with an ``edge_layout``) gathers nothing per
layer.
"""

from __future__ import annotations

import functools
import time
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import resolve_interpret

__all__ = ["TILE_NODES", "MAX_GRAPH_TILES", "DEFAULT_BLOCK_EDGES",
           "EdgeLayout", "build_layout", "edge_layer", "gated_edge_layer",
           "split_parts", "layout_stats", "reset_layout_stats"]

#: Node slots per tile: the one-hot height of a gather or a receiver sum.
TILE_NODES = 128
#: Tiles a graph may span; a larger graph is refused by the layout.
MAX_GRAPH_TILES = 2
#: Edge slots per grid step.
DEFAULT_BLOCK_EDGES = 512
#: bf16 parts of a float32 operand.
_PARTS = 3
#: Scoped VMEM for one grid step's blocks and temporaries, above the
#: 16 MiB default so that edge blocks of 2,048 slots fit (a v5e core has
#: 128 MiB).
_VMEM_LIMIT_BYTES = 64 << 20

#: Host work of :func:`build_layout`, summed over calls: nanoseconds,
#: edge slots made, of which padding, and node tiles.
_LAYOUT_STATS = {"layout_ns": 0, "edge_slots": 0, "edge_pad_slots": 0,
                 "tiles": 0}


def layout_stats() -> dict:
    """A copy of the layout counters (see :func:`reset_layout_stats`)."""
    return dict(_LAYOUT_STATS)


def reset_layout_stats() -> None:
    for key in _LAYOUT_STATS:
        _LAYOUT_STATS[key] = 0


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=["ids", "block_rtile", "block_stile", "edge_of_slot",
                 "slot_of_edge", "node_of_slot", "slot_of_node"],
    meta_fields=["block_edges"])
@dataclass(frozen=True)
class EdgeLayout:
    """Where each edge and node sits for :func:`gated_edge_layer`.

    ``ids`` (blocks, 2, block_edges) holds each edge slot's receiver and
    sender within its tile, -1 in padding; ``block_rtile`` / ``block_stile`` the
    receiver and sender tile of each block; ``edge_of_slot`` the edge in
    each slot (0 in padding) and ``slot_of_edge`` its inverse;
    ``node_of_slot`` the node in each node slot (0 in padding) and
    ``slot_of_node`` its inverse.
    """

    ids: jax.Array
    block_rtile: jax.Array
    block_stile: jax.Array
    edge_of_slot: jax.Array
    slot_of_edge: jax.Array
    node_of_slot: jax.Array
    slot_of_node: jax.Array
    block_edges: int

    def edge_state(self, e: jax.Array) -> jax.Array:
        """(E, d) edge values -> the kernel's edge state, (blocks, d_pad,
        block_edges): each block's slots contiguous, channels on rows.  A
        single row (1, d) is every edge's value, broadcast with no gather."""
        d = e.shape[1]
        n_blocks = self.ids.shape[0]
        if e.shape[0] == 1:
            blocks = jnp.broadcast_to(e.reshape(1, d, 1),
                                      (n_blocks, d, self.block_edges))
        else:
            blocks = e[self.edge_of_slot].reshape(
                n_blocks, self.block_edges, d).transpose(0, 2, 1)
        return jnp.pad(blocks, ((0, 0), (0, _round_up(d, 8) - d), (0, 0)))

    def to_slots(self, x: jax.Array) -> jax.Array:
        """Node rows (N, ...) -> node-slot rows; padding slots copy node 0."""
        return x[self.node_of_slot]

    def from_slots(self, x: jax.Array) -> jax.Array:
        """Node-slot rows -> node rows (N, ...), the inverse of
        :meth:`to_slots`."""
        return x[self.slot_of_node]

    def edge_values(self, state: jax.Array, d: int) -> jax.Array:
        """The inverse of :meth:`edge_state`: (E, d) edge values."""
        slots = state.transpose(0, 2, 1).reshape(-1, state.shape[1])
        return slots[self.slot_of_edge, :d]


def build_layout(senders, receivers, n_nodes: int, graph_ids=None, *,
                 block_edges: int = DEFAULT_BLOCK_EDGES) -> EdgeLayout:
    """The receiver-tiled layout of a batch of graphs (see module doc).

    ``graph_ids`` (N,) names each node's graph (None: one graph).  Raises
    ``ValueError`` for an edge between two graphs and for a graph of more
    than ``MAX_GRAPH_TILES * TILE_NODES`` nodes, whose senders would not
    fit the sender tiles of one edge block.
    """
    with jax.profiler.TraceAnnotation("repro.gatedgcn.layout"):
        t0 = time.perf_counter_ns()
        layout, n_slots, n_tiles = _build(senders, receivers, int(n_nodes),
                                          graph_ids, int(block_edges))
        _LAYOUT_STATS["layout_ns"] += time.perf_counter_ns() - t0
    _LAYOUT_STATS["edge_slots"] += n_slots
    _LAYOUT_STATS["edge_pad_slots"] += n_slots - int(np.asarray(senders).size)
    _LAYOUT_STATS["tiles"] += n_tiles
    return layout


def _build(senders, receivers, n, graph_ids, block_edges):
    snd = np.asarray(senders, np.int64)
    rcv = np.asarray(receivers, np.int64)
    T = TILE_NODES
    gid = (np.zeros(n, np.int64) if graph_ids is None
           else np.asarray(graph_ids, np.int64))
    if np.any(gid[snd] != gid[rcv]):
        raise ValueError("an edge joins two graphs: the gated edge layout "
                         "needs a block-diagonal batch")
    sizes = np.bincount(gid, minlength=1)
    if sizes.max() > MAX_GRAPH_TILES * T:
        g = int(np.argmax(sizes))
        raise ValueError(
            f"graph {g} has {int(sizes[g])} nodes: its senders do not fit "
            f"{MAX_GRAPH_TILES} tiles of {T} node slots, the most one edge "
            f"block gathers from; partition the graph first")
    # Whole graphs into tiles: a graph that does not fit the rest of the
    # current tile starts the next one.
    starts = np.zeros(sizes.size, np.int64)
    cursor = 0
    for g, size in enumerate(sizes.tolist()):
        room = T - cursor % T
        if size > room and room < T:
            cursor += room
        starts[g] = cursor
        cursor += size
    n_tiles = max(1, -(-cursor // T))
    node_order = np.argsort(gid, kind="stable")
    first = np.concatenate([[0], np.cumsum(sizes)[:-1]])
    slot_of_node = np.empty(n, np.int64)
    slot_of_node[node_order] = (starts[gid[node_order]]
                                + np.arange(n) - first[gid[node_order]])
    node_of_slot = np.zeros(n_tiles * T, np.int64)
    node_of_slot[slot_of_node] = np.arange(n)

    # Edge groups keyed (receiver tile, sender tile - receiver tile + 1).
    rs, ss = slot_of_node[rcv], slot_of_node[snd]
    span = 2 * MAX_GRAPH_TILES - 1
    key = (rs // T) * span + (ss // T - rs // T + MAX_GRAPH_TILES - 1)
    counts = np.bincount(key, minlength=n_tiles * span)
    blocks = -(-counts // block_edges)
    empty = blocks.reshape(n_tiles, span).sum(axis=1) == 0
    blocks.reshape(n_tiles, span)[empty, MAX_GRAPH_TILES - 1] = 1
    group_slot = np.concatenate([[0], np.cumsum(blocks)[:-1]]) * block_edges
    group_edge = np.concatenate([[0], np.cumsum(counts)[:-1]])
    order = np.argsort(key, kind="stable")
    slot = group_slot[key[order]] + np.arange(order.size) - group_edge[
        key[order]]
    n_slots = int(blocks.sum()) * block_edges
    ids = np.full((2, n_slots), -1, np.int32)
    ids[0, slot] = rs[order] % T
    ids[1, slot] = ss[order] % T
    ids = ids.reshape(2, -1, block_edges).transpose(1, 0, 2)
    edge_of_slot = np.zeros(n_slots, np.int64)
    edge_of_slot[slot] = order
    slot_of_edge = np.empty(order.size, np.int64)
    slot_of_edge[order] = slot
    group = np.repeat(np.arange(blocks.size), blocks)
    rtile = group // span
    stile = rtile + group % span - (MAX_GRAPH_TILES - 1)
    i32 = functools.partial(jnp.asarray, dtype=jnp.int32)
    layout = EdgeLayout(
        ids=i32(ids), block_rtile=i32(rtile), block_stile=i32(stile),
        edge_of_slot=i32(edge_of_slot), slot_of_edge=i32(slot_of_edge),
        node_of_slot=i32(node_of_slot), slot_of_node=i32(slot_of_node),
        block_edges=block_edges)
    return layout, n_slots, n_tiles


def _pad_rows(x: jax.Array, rows: int) -> jax.Array:
    return jnp.pad(x, ((0, rows - x.shape[0]),) + ((0, 0),) * (x.ndim - 1))


def _truncate_bf16(x: jax.Array) -> jax.Array:
    """``x`` with its mantissa cut to bf16's 8 bits, still float32: the
    cut is exact in bf16, so no cast can round or fold it away."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                        jnp.float32)


def _parts(x: jax.Array) -> tuple:
    """float32 ``x`` -> bf16 ``(hi, mid, lo)`` with ``hi + mid + lo == x``
    exactly in float32."""
    parts = []
    for _ in range(_PARTS):
        p = _truncate_bf16(x)
        parts.append(p.astype(jnp.bfloat16))
        x = x - p
    return tuple(parts)


def split_parts(x: jax.Array) -> jax.Array:
    """float32 (R, C) -> bf16 (3R, C): :func:`_parts` stacked."""
    return jnp.concatenate(_parts(x), axis=0)


def _stacked_c(c: jax.Array, dp: int) -> jax.Array:
    """``C`` (d, d, as ``e @ C``) -> bf16 (dp, 6 dp): the parts of ``Cᵀ``
    that pair with ``[hi; hi; hi; mid; mid; lo]`` of the edge state."""
    d = c.shape[0]
    hi, mid, lo = _parts(jnp.pad(c.T, ((0, dp - d), (0, dp - d))))
    return jnp.concatenate([hi, mid, lo, hi, mid, hi], axis=1)


def _sum_parts(y: jax.Array, rows: int) -> jax.Array:
    """Rows ``[k*rows, (k+1)*rows)`` of ``y`` summed over the parts."""
    out = y[:rows]
    for k in range(1, _PARTS):
        out = out + y[k * rows:(k + 1) * rows]
    return out


def _kernel(rtile_ref, stile_ref, e_ref, ids_ref, pr_ref, ps_ref, c_ref,
            scale_ref, shift_ref, e_out_ref, acc_ref):
    b = pl.program_id(0)
    dp = e_ref.shape[0]
    first = (b == 0) | (rtile_ref[b] != rtile_ref[jnp.maximum(b - 1, 0)])

    @pl.when(first)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    e = e_ref[...]
    ids = ids_ref[...]
    rows = jax.lax.broadcasted_iota(jnp.int32, (TILE_NODES, e.shape[1]), 0)
    oh_r = (rows == ids[0:1, :]).astype(jnp.bfloat16)
    oh_s = (rows == ids[1:2, :]).astype(jnp.bfloat16)

    # bf16 x bf16 contractions take Mosaic's default precision, the only
    # one it accepts for them, whatever precision the caller set.
    bf16_dot = functools.partial(jax.lax.dot_general,
                                 precision=jax.lax.Precision.DEFAULT,
                                 preferred_element_type=jnp.float32)

    def gather(p_ref, oh):
        return bf16_dot(p_ref[...], oh, (((1,), (0,)), ((), ())))

    d_h = _sum_parts(gather(pr_ref, oh_r), dp)
    eb_h = _sum_parts(gather(ps_ref, oh_s), 2 * dp)
    hi, mid, lo = _parts(e)
    e6 = jnp.concatenate([hi, hi, hi, mid, mid, lo], axis=0)
    e_hat = (bf16_dot(c_ref[...], e6, (((1,), (0,)), ((), ())))
             + d_h + eb_h[:dp])
    e_out_ref[...] = e + jnp.maximum(
        e_hat * scale_ref[...] + shift_ref[...], 0.0)
    sig = 1.0 / (1.0 + jnp.exp(-e_hat))
    msg = jnp.concatenate([sig * eb_h[dp:], sig], axis=0)
    sums = bf16_dot(split_parts(msg), oh_r, (((1,), (1,)), ((), ())))
    acc_ref[...] += _sum_parts(sums, 2 * dp)


@functools.partial(jax.jit, static_argnames=("interpret",))
def gated_edge_layer(e, pr, ps, c6, scale, shift, ids, rtile, stile, *,
                     interpret: Optional[bool] = None):
    """The kernel call alone, on operands already in the layout:

    ``e`` (blocks, dp, block_edges) f32, updated in place; ``pr`` (3dp,
    node slots) and ``ps`` (6dp, node slots) bf16 parts of ``D h`` and
    ``[E h; B h]``, transposed; ``c6`` (dp, 6dp) bf16 parts of ``Cᵀ``
    (:func:`_stacked_c`); ``scale``, ``shift`` (dp, 1); ``ids`` (blocks,
    2, block_edges); ``rtile``, ``stile`` (blocks,).  Returns ``e'`` like
    ``e`` and the receiver sums (2dp, node slots): numerator rows, then
    denominator.
    """
    n_blocks, dp, be = e.shape
    n_node_slots = pr.shape[1]
    T = TILE_NODES
    full = lambda shape: pl.BlockSpec(shape, lambda b, r, s: (0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((None, dp, be), lambda b, r, s: (b, 0, 0)),
            pl.BlockSpec((None, 2, be), lambda b, r, s: (b, 0, 0)),
            pl.BlockSpec((pr.shape[0], T), lambda b, r, s: (0, r[b])),
            pl.BlockSpec((ps.shape[0], T), lambda b, r, s: (0, s[b])),
            full(c6.shape), full((dp, 1)), full((dp, 1)),
        ],
        out_specs=[
            pl.BlockSpec((None, dp, be), lambda b, r, s: (b, 0, 0)),
            pl.BlockSpec((2 * dp, T), lambda b, r, s: (0, r[b])),
        ],
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(e.shape, jnp.float32),
                   jax.ShapeDtypeStruct((2 * dp, n_node_slots), jnp.float32)],
        # e' overwrites e: a scan over layers then carries one buffer.
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=resolve_interpret(interpret),
        name="gated_edge_layer",
    )(rtile, stile, e, ids, pr, ps, c6, scale, shift)


def edge_layer(e, proj, edge_params: dict, layout: EdgeLayout, *,
               interpret: Optional[bool] = None):
    """The edge half of a layer from operands in the layout's node-slot
    order (:meth:`EdgeLayout.to_slots`).

    ``e`` is the layout's edge state (:meth:`EdgeLayout.edge_state`);
    ``proj`` (node slots, 3d) holds ``[D h + biases | E h | B h]``;
    ``edge_params`` holds ``C`` (d, d, as ``e @ C``) and eval BatchNorm's
    folded ``scale`` and ``shift`` (d,).  Returns ``(e', num, den)`` with
    ``num``, ``den`` (node slots, d).
    """
    if "scale" not in edge_params:
        raise ValueError("the gated edge kernel folds eval-mode BatchNorm "
                         "only; this layer normalises edges otherwise")
    c = edge_params["C"]
    d = c.shape[0]
    dp = e.shape[1]
    p = proj.T
    pr = split_parts(_pad_rows(p[:d], dp))
    ps = split_parts(jnp.concatenate([_pad_rows(p[d:2 * d], dp),
                                      _pad_rows(p[2 * d:], dp)]))
    col = lambda v: _pad_rows(v.astype(jnp.float32)[:, None], dp)
    e_new, sums = gated_edge_layer(
        e, pr, ps, _stacked_c(c, dp), col(edge_params["scale"]),
        col(edge_params["shift"]), layout.ids, layout.block_rtile,
        layout.block_stile, interpret=interpret)
    return e_new, sums[:d].T, sums[dp:dp + d].T
