"""GatedGCN's fused gated edge kernel and its layout (interpret mode on the
CPU), and the model through it, against plain references on seeded random
weights."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import gatedgcn as gatedgcn_cfg
from repro.kernels import gated_edge as ge
from repro.kernels import ops, ref
from repro.models.gnn import gatedgcn
from repro.models.gnn.graph import GraphBatch

D = 70          # the published width


def _rel(a, b):
    return float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-30))


def batch(sizes, seed=0, p=0.3, isolated=()):
    """Symmetric simple random graphs, concatenated: (snd, rcv, gid, n).
    Nodes listed in ``isolated`` (batch ids) lose their edges."""
    rng = np.random.default_rng(seed)
    snd, rcv, gid, off = [], [], [], 0
    for g, n in enumerate(sizes):
        a = np.triu(rng.random((n, n)) < p, 1)
        a = a | a.T
        i, j = np.nonzero(a)
        snd.append(j + off)
        rcv.append(i + off)
        gid += [g] * n
        off += n
    snd, rcv = np.concatenate(snd), np.concatenate(rcv)
    keep = ~np.isin(snd, isolated) & ~np.isin(rcv, isolated)
    return snd[keep], rcv[keep], np.asarray(gid), off


# one tile; many tiles; a graph filling a tile exactly; a graph over two
# tiles; an isolated node; every case pads its last block
CASES = {
    "one_tile": ([40], 128, ()),
    "many_tiles": ([60, 100, 30, 90, 50], 256, ()),
    "fills_a_tile": ([128, 20], 128, ()),
    "two_tile_graph": ([200, 40], 512, ()),
    "no_in_edges": ([50, 70], 128, (3, 60)),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_matches_edge_list_reference(case):
    sizes, be, isolated = CASES[case]
    snd, rcv, gid, n = batch(sizes, isolated=isolated)
    layout = ge.build_layout(snd, rcv, n, gid, block_edges=be)
    k = jax.random.split(jax.random.key(sum(sizes)), 5)
    e = jax.random.normal(k[0], (snd.size, D))
    proj = jax.random.normal(k[1], (n, 3 * D))
    c = jax.random.normal(k[2], (D, D)) / np.sqrt(D)
    scale = jax.random.normal(k[3], (D,))
    shift = jax.random.normal(k[4], (D,))
    e_new, num, den = ops.gated_edge_layer(
        layout.edge_state(e), layout.to_slots(proj),
        {"C": c, "scale": scale, "shift": shift}, layout)
    num, den = layout.from_slots(num), layout.from_slots(den)
    e_ref, num_ref, den_ref = ref.gated_edge_layer_ref(
        e, proj, c, scale, shift, snd, rcv, n)
    assert _rel(layout.edge_values(e_new, D), e_ref) < 2e-6
    assert _rel(num, num_ref) < 2e-6 and _rel(den, den_ref) < 2e-6
    for node in isolated:
        assert float(jnp.max(jnp.abs(den[node]))) == 0.0


def test_layout_invariants():
    snd, rcv, gid, n = batch([60, 100, 200, 30, 128, 5], p=0.2)
    ge.reset_layout_stats()
    layout = ge.build_layout(snd, rcv, n, gid, block_edges=128)
    ids = np.asarray(layout.ids).transpose(1, 0, 2).reshape(2, -1)
    real = ids[0] >= 0
    slot_of_node = np.asarray(layout.slot_of_node)
    rt, st = np.asarray(layout.block_rtile), np.asarray(layout.block_stile)
    # every edge sits once, under its receiver's and sender's tiles
    slot = np.asarray(layout.slot_of_edge)
    assert real.sum() == snd.size and (np.asarray(layout.edge_of_slot)[slot]
                                       == np.arange(snd.size)).all()
    blk = slot // 128
    T = ge.TILE_NODES
    assert (rt[blk] * T + ids[0, slot] == slot_of_node[rcv]).all()
    assert (st[blk] * T + ids[1, slot] == slot_of_node[snd]).all()
    # each receiver tile has blocks, all consecutive; graphs stay whole
    n_tiles = np.asarray(layout.node_of_slot).size // T
    assert sorted(set(rt.tolist())) == list(range(n_tiles))
    assert (np.diff(rt) >= 0).all()
    for g in range(gid.max() + 1):
        s = slot_of_node[gid == g]
        assert s.max() - s.min() == s.size - 1
        assert s.size > T or s.min() // T == s.max() // T
    stats = ge.layout_stats()
    assert stats["edge_slots"] == ids.shape[1] and stats["tiles"] == n_tiles
    assert stats["edge_pad_slots"] == ids.shape[1] - snd.size
    assert stats["layout_ns"] > 0


def test_edge_state_of_one_row_and_slot_order():
    """A single row stands for every edge; node rows go to slots and back."""
    snd, rcv, gid, n = batch([60, 100, 30], p=0.2)
    layout = ge.build_layout(snd, rcv, n, gid, block_edges=128)
    row = jax.random.normal(jax.random.key(4), (1, D))
    one = layout.edge_state(row)
    every = layout.edge_state(jnp.broadcast_to(row, (snd.size, D)))
    assert one.shape == every.shape
    assert jnp.array_equal(layout.edge_values(one, D),
                           layout.edge_values(every, D))
    assert not jnp.any(one[:, D:])
    x = jax.random.normal(jax.random.key(5), (n, 3))
    slots = layout.to_slots(x)
    assert slots.shape == (np.asarray(layout.node_of_slot).size, 3)
    assert jnp.array_equal(layout.from_slots(slots), x)


def test_layout_refuses_a_graph_over_its_tiles():
    snd, rcv, gid, n = batch([40, 2 * ge.TILE_NODES + 1], p=0.05)
    with pytest.raises(ValueError, match="partition the graph"):
        ge.build_layout(snd, rcv, n, gid)


def test_layout_refuses_an_edge_between_graphs():
    with pytest.raises(ValueError, match="block-diagonal"):
        ge.build_layout(np.array([0, 5]), np.array([5, 0]), 10,
                        np.array([0] * 5 + [1] * 5))


def test_kernel_hook_refuses_layer_norm():
    snd, rcv, gid, n = batch([20])
    layout = ge.build_layout(snd, rcv, n, gid)
    ep = {"C": jnp.eye(D), "ln_s": jnp.ones(D), "ln_b": jnp.zeros(D)}
    with pytest.raises(ValueError, match="BatchNorm"):
        ops.gated_edge_layer(layout.edge_state(jnp.ones((snd.size, D))),
                             jnp.zeros((n, 3 * D)), ep, layout)


def reference_forward(cfg, params, labels, snd, rcv, n):
    """The published model written straight from its equations, float32 at
    ``Precision.HIGHEST``: a loop over layers, η normalised per edge."""
    mm = lambda a, b: jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST)
    seg = lambda x: jax.ops.segment_sum(x, rcv, num_segments=n)

    def bn(x, p):
        return (x - p["mean"]) / jnp.sqrt(p["var"] + 1e-5) * p["gamma"] \
            + p["beta"]

    h = params["embed_h"][labels]
    e = mm(jnp.ones((snd.size, 1)), params["embed_e"]) + params["embed_e_b"]
    for layer in range(cfg.n_layers):
        lp = jax.tree_util.tree_map(lambda x: x[layer], params["layers"])
        lin = lambda name, x: mm(x, lp[name]) + lp["b" + name]
        e_hat = lin("C", e) + lin("D", h)[rcv] + lin("E", h)[snd]
        sig = jax.nn.sigmoid(e_hat)
        eta = sig / (seg(sig)[rcv] + 1e-6)
        h_new = h + jax.nn.relu(bn(lin("A", h) + seg(eta * lin("B", h)[snd]),
                                   lp["bn_h"]))
        e = e + jax.nn.relu(bn(e_hat, lp["bn_e"]))
        h = h_new
    for i, p in enumerate(params["mlp"]):
        h = mm(h, p["w"]) + p["b"]
        h = jax.nn.relu(h) if i < len(params["mlp"]) - 1 else h
    return h


def test_pattern_model_kernel_jnp_and_reference_agree():
    cfg = gatedgcn_cfg.make_pattern_config(n_layers=3)
    snd, rcv, gid, n = batch([30, 130, 45], seed=3, p=0.25)
    labels = np.random.default_rng(3).integers(0, 3, n)
    g = GraphBatch(node_feat=jnp.asarray(labels, jnp.int32),
                   senders=jnp.asarray(snd, jnp.int32),
                   receivers=jnp.asarray(rcv, jnp.int32),
                   graph_ids=jnp.asarray(gid, jnp.int32), n_graphs=3)
    params = gatedgcn.init_params(cfg, jax.random.key(7))
    layout = ge.build_layout(snd, rcv, n, gid, block_edges=256)
    with jax.default_matmul_precision("highest"):
        plain = gatedgcn.forward(cfg, params, g, remat=False)
        fused = gatedgcn.forward(cfg, params, g, remat=False,
                                 edge_layer_fn=ops.gated_edge_layer,
                                 edge_layout=layout)
        chosen = gatedgcn.forward(cfg, params, g, remat=False,
                                  edge_layout=layout)
    expect = reference_forward(cfg, params, labels, snd, rcv, n)
    assert jnp.array_equal(chosen, fused)
    assert fused.shape == (n, 2)
    assert _rel(plain, expect) < 1e-5
    assert _rel(fused, expect) < 1e-5


def test_pattern_preset_is_the_published_model():
    cfg = gatedgcn_cfg.make_pattern_config()
    assert (cfg.n_layers, cfg.d_hidden, cfg.n_classes) == (16, 70, 2)
    assert cfg.norm == "batch" and cfg.d_in == 3
    assert cfg.readout_hidden == (35, 17) and cfg.d_edge_in == 1
    p = gatedgcn.init_params(cfg, jax.random.key(0))
    assert p["embed_h"].shape == (3, 70)
    assert [m["w"].shape for m in p["mlp"]] == [(70, 35), (35, 17), (17, 2)]
    assert p["layers"]["bn_e"]["var"].shape == (16, 70)
    defaults = gatedgcn.GatedGCNConfig()
    assert defaults.norm == "layer" and defaults.readout_hidden == ()
    dp = gatedgcn.init_params(defaults, jax.random.key(0))
    assert dp["embed_h"].shape == (defaults.d_in, 70) and "out" in dp
    assert "bA" not in dp["layers"] and "embed_e_b" not in dp


def test_default_gate_is_taken_before_the_norm():
    """σ(ê), not σ(e'): the denominator of a receiver is Σ σ(ê) over its
    in-edges."""
    cfg = gatedgcn.GatedGCNConfig(n_layers=1, d_in=4, d_edge_in=2,
                                  d_hidden=8, n_classes=2)
    snd, rcv, _, n = batch([12], seed=5, p=0.4)
    g = GraphBatch(node_feat=jnp.ones((n, 4)), senders=jnp.asarray(snd),
                   receivers=jnp.asarray(rcv))
    k = jax.random.split(jax.random.key(2), 3)
    e = jax.random.normal(k[0], (snd.size, 8))
    proj = jax.random.normal(k[1], (n, 24))
    ep = {"C": jax.random.normal(k[2], (8, 8)), "ln_s": jnp.ones(8),
          "ln_b": jnp.zeros(8)}
    _, _, den = gatedgcn.edge_layer_jnp(e, proj, ep, g)
    e_hat = e @ ep["C"] + proj[rcv, :8] + proj[snd, 8:16]
    expect = jax.ops.segment_sum(jax.nn.sigmoid(e_hat), rcv, num_segments=n)
    assert _rel(den, expect) < 1e-6
