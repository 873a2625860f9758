"""GatedGCN (Bresson & Laurent, arXiv:1711.07553; config from Dwivedi et al.,
arXiv:2003.00982).

Edge-gated message passing, with i the receiver and j the sender of edge
ij (D on the receiver and E on the sender; the DGL reference names them
the other way round, which with random weights only swaps two names):
    ê_ij  = C e_ij + D h_i + E h_j
    e'_ij = e_ij + ReLU(norm_e(ê_ij))
    η_ij  = σ(ê_ij) / (Σ_j' σ(ê_ij') + 1e-6)
    h'_i  = h_i + ReLU(norm_h(A h_i + Σ_j η_ij ⊙ B h_j))

``norm`` is LayerNorm by default (batch statistics don't shard; DESIGN.md),
with a dense input projection and no biases.  ``norm="batch"`` is the
published model: BatchNorm in eval mode, γ(x − μ)/√(σ² + 1e-5) + β with
the running μ, σ² held in the parameters, biases on every Linear, and
``node_feat`` holding integer labels embedded from a table of ``d_in``
rows.  ``readout_hidden`` gives the hidden widths of an MLP readout; the
default reads out linearly.

The edge half of each layer is a hook, ``edge_layer_fn(e, proj, ep,
edges)``: ``proj`` holds ``[D h + b_D + b_C | E h + b_E | B h + b_B]`` by
node, ``ep`` the edge parameters (``C`` and the edge norm's), and it
returns ``(e', num, den)`` with the sums by receiver.  The default,
:func:`edge_layer_jnp`, gathers and segment-sums over the batch's edge
list; ``repro.kernels.ops.gated_edge_layer`` runs the fused kernel over
an ``edge_layout`` (``repro.kernels.gated_edge.build_layout``), in whose
edge state ``e`` then lives and in whose node-slot order the layers keep
``h``, so that no layer gathers node rows.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from ..common import dense_init, layer_norm
from .graph import GraphBatch
from .layers import scatter_sum

Array = jax.Array

BN_EPS = 1e-5


@dataclass(frozen=True)
class GatedGCNConfig:
    name: str = "gatedgcn"
    n_layers: int = 16
    d_in: int = 16
    d_edge_in: int = 16
    d_hidden: int = 70
    n_classes: int = 10
    readout: str = "nodes"        # "nodes" | "graphs"
    norm: str = "layer"           # "layer" | "batch": the published model
    readout_hidden: tuple = ()    # hidden widths of the readout MLP


def _bn_params(k: Array, d: int, dtype) -> dict:
    """Eval-mode BatchNorm: affine and running statistics from the seed.

    γ is drawn near 0.3, so each residual branch adds about a third of the
    stream: with γ near 1 sixteen random layers are chaotic, and a float32
    forward drifts 1e-4 from a float64 one on some seeds."""
    kg, kb, km, kv = jax.random.split(k, 4)
    return {"gamma": (0.3 + 0.1 * jax.random.normal(kg, (d,))).astype(dtype),
            "beta": (0.1 * jax.random.normal(kb, (d,))).astype(dtype),
            "mean": (0.1 * jax.random.normal(km, (d,))).astype(dtype),
            "var": jax.random.uniform(kv, (d,), minval=0.5,
                                      maxval=2.0).astype(dtype)}


def _bias(k: Array, d: int, fan_in: int, dtype) -> Array:
    bound = 1.0 / fan_in ** 0.5
    return jax.random.uniform(k, (d,), minval=-bound,
                              maxval=bound).astype(dtype)


def init_params(cfg: GatedGCNConfig, rng: Array, *, dtype=jnp.float32) -> dict:
    d = cfg.d_hidden
    published = cfg.norm == "batch"
    k_in, k_ein, k_out, *keys = jax.random.split(rng, 3 + cfg.n_layers)

    def layer(k):
        ks = jax.random.split(k, 5)
        p = {name: dense_init(kk, (d, d), dtype=dtype)
             for name, kk in zip("ABCDE", ks)}
        extra = jax.random.split(jax.random.fold_in(k, 1), 7)
        if published:
            p["bn_h"] = _bn_params(extra[0], d, dtype)
            p["bn_e"] = _bn_params(extra[1], d, dtype)
            for name, kk in zip("ABCDE", extra[2:]):
                p["b" + name] = _bias(kk, d, d, dtype)
        else:
            p.update(ln_h_s=jnp.ones((d,), dtype), ln_h_b=jnp.zeros((d,), dtype),
                     ln_e_s=jnp.ones((d,), dtype), ln_e_b=jnp.zeros((d,), dtype))
        return p

    # Stack layers for lax.scan.
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs),
                                     *[layer(k) for k in keys])
    if published:
        embed_h = jax.random.normal(k_in, (cfg.d_in, d)).astype(dtype)
    else:
        embed_h = dense_init(k_in, (cfg.d_in, d), dtype=dtype)
    params = {"embed_h": embed_h,
              "embed_e": dense_init(k_ein, (cfg.d_edge_in, d), dtype=dtype),
              "layers": stacked}
    if published:
        params["embed_e_b"] = _bias(jax.random.fold_in(k_ein, 1), d,
                                    cfg.d_edge_in, dtype)
    widths = (d, *cfg.readout_hidden, cfg.n_classes)
    if cfg.readout_hidden:
        ks = jax.random.split(k_out, len(widths) - 1)
        params["mlp"] = [
            {"w": dense_init(kk, (a, b), dtype=dtype),
             "b": _bias(jax.random.fold_in(kk, 1), b, a, dtype)}
            for kk, a, b in zip(ks, widths[:-1], widths[1:])]
    else:
        params["out"] = dense_init(k_out, (d, cfg.n_classes), dtype=dtype)
    return params


def _bn_affine(bn: dict) -> tuple[Array, Array]:
    """Eval-mode BatchNorm folded to ``x * scale + shift``."""
    scale = bn["gamma"] * jax.lax.rsqrt(bn["var"] + BN_EPS)
    return scale, bn["beta"] - bn["mean"] * scale


def _norm(x: Array, lp: dict, side: str) -> Array:
    """The layer's normalisation of the node (``h``) or edge (``e``) side."""
    if "bn_" + side in lp:
        scale, shift = _bn_affine(lp["bn_" + side])
        return x * scale + shift
    return layer_norm(x, lp[f"ln_{side}_s"], lp[f"ln_{side}_b"])


def _edge_params(lp: dict) -> dict:
    """C and the edge normalisation, eval BatchNorm folded to an affine."""
    if "bn_e" in lp:
        scale, shift = _bn_affine(lp["bn_e"])
        return {"C": lp["C"], "scale": scale, "shift": shift}
    return {"C": lp["C"], "ln_s": lp["ln_e_s"], "ln_b": lp["ln_e_b"]}


def edge_layer_jnp(e: Array, proj: Array, ep: dict, g: GraphBatch):
    """The edge half of a layer as gathers and segment sums over ``g``'s
    edge list; masked edges send nothing."""
    snd, rcv = g.senders, g.receivers
    # Whole rows are gathered: the TPU compiler turns a gather of a column
    # slice (slice sizes (1, d) of an (N, 3d) operand) into a loop over
    # the edges, which made a PATTERN forward take 93 s on a TPU v5e.
    d_h, e_h, b_h = jnp.split(proj, 3, axis=1)
    e_hat = e @ ep["C"] + d_h[rcv] + e_h[snd]
    if "scale" in ep:
        normed = e_hat * ep["scale"] + ep["shift"]
    else:
        normed = layer_norm(e_hat, ep["ln_s"], ep["ln_b"])
    sig = jax.nn.sigmoid(e_hat) * g.emask()[:, None]
    num = scatter_sum(sig * b_h[snd], rcv, g.n_nodes)
    den = scatter_sum(sig, rcv, g.n_nodes)
    return e + jax.nn.relu(normed), num, den


def forward(cfg: GatedGCNConfig, params: dict, g: GraphBatch,
            *, policy=None, remat: bool = True,
            edge_layer_fn: Optional[Callable] = None,
            edge_layout=None) -> Array:
    """Logits by node (or by graph with ``readout="graphs"``).

    ``edge_layer_fn`` computes each layer's edge half over ``edge_layout``
    if given, else over ``g``; by default the fused kernel
    (``repro.kernels.ops.gated_edge_layer``) where there is a layout, and
    :func:`edge_layer_jnp` where there is none."""
    if cfg.norm == "batch":
        h = params["embed_h"][g.node_feat.reshape(-1).astype(jnp.int32)]
    else:
        h = g.node_feat @ params["embed_h"]
    # Without edge features every edge starts from one row.
    e = (g.edge_feat if g.edge_feat is not None
         else jnp.ones((1, cfg.d_edge_in), h.dtype)) @ params["embed_e"]
    if "embed_e_b" in params:
        e = e + params["embed_e_b"]
    if edge_layout is None:
        edge_layer = edge_layer_fn or edge_layer_jnp
        edges = g
        e = jnp.broadcast_to(e, (g.n_edges, e.shape[1]))
    else:
        from ...kernels import ops

        edge_layer = edge_layer_fn or ops.gated_edge_layer
        edges = edge_layout
        e = edge_layout.edge_state(e)
        h = edge_layout.to_slots(h)

    def body(carry, lp):
        h, e = carry
        proj = h @ jnp.concatenate([lp["D"], lp["E"], lp["B"]], axis=1)
        agg = h @ lp["A"]
        if "bA" in lp:
            proj = proj + jnp.concatenate([lp["bD"] + lp["bC"], lp["bE"],
                                           lp["bB"]])
            agg = agg + lp["bA"]
        e, num, den = edge_layer(e, proj, _edge_params(lp), edges)
        h = h + jax.nn.relu(_norm(agg + num / (den + 1e-6), lp, "h"))
        return (h, e), None

    scan_body = jax.checkpoint(
        body, policy=jax.checkpoint_policies.nothing_saveable) if remat else body
    (h, e), _ = jax.lax.scan(scan_body, (h, e), params["layers"])
    if edge_layout is not None:
        h = edge_layout.from_slots(h)
    if cfg.readout == "graphs":
        pooled = jax.ops.segment_sum(h * g.nmask()[:, None], g.graph_ids,
                                     num_segments=g.n_graphs)
        cnt = jax.ops.segment_sum(g.nmask(), g.graph_ids, num_segments=g.n_graphs)
        h = pooled / jnp.maximum(cnt, 1.0)[:, None]
    if "mlp" not in params:
        return h @ params["out"]
    for i, lin in enumerate(params["mlp"]):
        h = h @ lin["w"] + lin["b"]
        if i < len(params["mlp"]) - 1:
            h = jax.nn.relu(h)
    return h


def loss_fn(cfg: GatedGCNConfig, params: dict, g: GraphBatch,
            *, policy=None) -> tuple[Array, dict]:
    logits = forward(cfg, params, g, policy=policy)
    if cfg.readout == "graphs":
        labels = g.labels
        logz = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
        gold = jnp.take_along_axis(logits.astype(jnp.float32),
                                   labels[:, None], axis=-1)[:, 0]
        loss = jnp.mean(logz - gold)
        acc = jnp.mean(jnp.argmax(logits, -1) == labels)
    else:
        mask = g.nmask()
        logz = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
        gold = jnp.take_along_axis(logits.astype(jnp.float32),
                                   g.labels[:, None], axis=-1)[:, 0]
        loss = jnp.sum((logz - gold) * mask) / jnp.maximum(jnp.sum(mask), 1.0)
        acc = jnp.sum((jnp.argmax(logits, -1) == g.labels) * mask) / jnp.maximum(
            jnp.sum(mask), 1.0)
    return loss, {"loss": loss, "acc": acc}
