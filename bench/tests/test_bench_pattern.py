"""The pattern.infer and cora.infer_unfused cells: the PATTERN generator,
the float64 GatedGCN reference, the four new metric readers, and whole
runs at small sizes on the CPU (sound runs correct, controls not)."""

import json
import math
import time

import numpy as np
import pytest

import controls_gated
import gated_reference
import gated_work
import harness
import patterngen
import work

CFG = harness.load_json(harness.ROOT / "bench/configs/gatedgcn-pattern.json")
TOTALS = ("n_nodes", "n_edges", "n_undirected_edges")


def small(n_graphs, **kw):
    cfg = {k: v for k, v in CFG.items() if k not in TOTALS}
    cfg.update(n_graphs=n_graphs, **kw)
    return cfg


def test_generator_is_fixed_by_its_seed():
    a = patterngen.pattern_batch(small(5))
    b = patterngen.pattern_batch(small(5))
    c = patterngen.pattern_batch(small(5, graph_seed=CFG["graph_seed"] + 1))
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[0], c[0]) or a[0].size != c[0].size


def test_generator_makes_simple_symmetric_pattern_graphs():
    snd, rcv, gid, labels = patterngen.pattern_batch(small(20))
    sizes = np.bincount(gid)
    lo = CFG["communities"] * CFG["community_size"][0] + CFG["pattern_nodes"]
    hi = CFG["communities"] * CFG["community_size"][1] + CFG["pattern_nodes"]
    assert sizes.size == 20 and sizes.min() >= lo and sizes.max() <= hi
    assert (np.diff(gid) >= 0).all() and (gid[snd] == gid[rcv]).all()
    assert (snd != rcv).all()
    n = gid.size
    pairs = snd.astype(np.int64) * n + rcv
    assert np.unique(pairs).size == pairs.size
    assert np.array_equal(np.sort(pairs), np.sort(rcv.astype(np.int64) * n
                                                  + snd))
    assert set(np.unique(labels).tolist()) <= {0, 1, 2}


def test_configuration_states_the_batch_made():
    snd, rcv, gid, _ = patterngen.pattern_batch(CFG)
    assert gid.size == CFG["n_nodes"] and snd.size == CFG["n_edges"]
    assert CFG["n_edges"] == 2 * CFG["n_undirected_edges"]
    assert np.bincount(gid).size == CFG["n_graphs"] == 128
    with pytest.raises(RuntimeError, match="n_edges"):
        patterngen.pattern_batch(dict(CFG, n_edges=CFG["n_edges"] + 2))


def test_reference_on_a_hand_computed_pair():
    """Two nodes joined both ways, width 1, every weight 1, BN the
    identity: ê = 0.5 + h_i + h_j = 3.5 on both edges, η = σ/(σ + 1e-6),
    h' = h + (h + η h_other)."""
    one = np.ones((1, 1, 1))
    identity = {"gamma": np.ones((1, 1)), "beta": np.zeros((1, 1)),
                "mean": np.zeros((1, 1)), "var": np.ones((1, 1)) - 1e-5}
    layers = {k: one for k in "ABCDE"}
    layers.update({"b" + k: np.zeros((1, 1)) for k in "ABCDE"})
    layers.update(bn_h=identity, bn_e=identity)
    params = {"embed_h": np.array([[1.0], [2.0], [3.0]]),
              "embed_e": np.array([[0.5]]), "embed_e_b": np.zeros(1),
              "layers": layers,
              "mlp": [{"w": np.ones((1, 1)), "b": np.zeros(1)}]}
    out = gated_reference.gatedgcn_forward(params, np.array([0, 1]),
                                           np.array([0, 1]), np.array([1, 0]))
    sig = 1.0 / (1.0 + math.exp(-3.5))
    eta = sig / (sig + 1e-6)
    assert np.allclose(out[:, 0], [2.0 + 2.0 * eta, 4.0 + eta], rtol=1e-15)


def _view(cfg, record, trace):
    peak = next(iter(harness.load_json(harness.BENCH / "peaks.json")
                     .values()))
    return harness.MetricView(config=cfg, traffic={}, record=record,
                              trace=trace, peak=peak), peak


def _read(name, view):
    return harness.load_module(harness.BENCH / "metrics" / f"{name}.py",
                               "t_" + name).read(view)


TRACE = {
    "devices": {"/device:TPU:0": {
        "ops": [["%gated_edge_layer.6 = (f32[72,8]) custom-call(a)", 100,
                 400],
                ["%fusion.1 = f32[8,70] fusion(b)", 500, 50],
                ["%gated_edge_layer.6 = (f32[72,8]) custom-call(c)", 600,
                 400],
                ["%gnn_aggregate.2 = f32[256,32] custom-call(d)", 1100, 60],
                ["%gnn_combine.3 = f32[256,7] custom-call(e)", 1200, 40]],
        "modules": [["jit_fwd(1)", 90, 1200]]}},
    "spans": [["bench.window", 50, 2000]],
}


def test_gated_metric_readers():
    record = {"forwards": 2, "edges": 1000, "nodes": 50,
              "layout": {"edge_slots": 1200, "edge_pad_slots": 200,
                         "layout_ns": 5, "tiles": 1}}
    view, peak = _view(CFG, record, TRACE)
    assert _read("gated_edge_ms", view) == pytest.approx(800 / 1e6 / 2)
    need = gated_work.gatedgcn_edge_layer(1000, 50, 70)
    least = work.least_seconds(need, peak) * CFG["n_layers"] * 2
    assert _read("gated_edge_roofline", view) == pytest.approx(
        100 * least / 800e-9)
    assert _read("gated_pad_share", view) == pytest.approx(100 * 200 / 1200)
    for name in ("gated_edge_ms", "gated_edge_roofline", "gated_pad_share"):
        assert _read(name, _view(CFG, {"forwards": 2}, None)[0]) is None


def test_unfused_metric_reader():
    cora = harness.load_json(harness.ROOT / "bench/configs/gcn-cora.json")
    view, peak = _view(cora, {"forwards": 3, "nnz": 13264}, TRACE)
    need = work.gcn_forward(2708, 13264, (1433, 16, 7))
    least = work.least_seconds(need, peak) * 3
    assert _read("unfused_roofline", view) == pytest.approx(
        100 * least / 100e-9)
    assert _read("unfused_roofline", _view(cora, {"forwards": 3, "nnz": 1},
                                           None)[0]) is None


def test_edge_layer_work():
    w = gated_work.gatedgcn_edge_layer(794_510, 15_443, 70)
    assert w.flops == 2 * 794_510 * 70 * 70 + 12 * 794_510 * 70
    assert w.bytes == 4 * (2 * 794_510 * 70 + 5 * 15_443 * 70) + 8 * 794_510


def _small_pattern():
    cfg = small(3, n_layers=3)
    snd, _, gid, _ = patterngen.pattern_batch(cfg)
    return {k: cfg[k] for k in ("n_graphs", "n_layers")} | {
        "n_nodes": int(gid.size), "n_edges": int(snd.size),
        "n_undirected_edges": int(snd.size) // 2}


SMALL = {
    "pattern.infer": _small_pattern,
    "cora.infer_unfused": lambda: {"name": "small-cora", "n_nodes": 700,
                                   "n_edges": 2_800,
                                   "n_undirected_edges": 1_400, "d_in": 384},
}
DRIVER = {"pattern.infer": "infer_gated",
          "cora.infer_unfused": "infer_unfused"}


def run(cell, tmp_path, hook=None, trace=False):
    overrides = {"config": SMALL[cell](), "data_dir": str(tmp_path / "data")}
    return harness.run_cell(cell, 2**33 + 12_345, 0.3, trace,
                            t_start=time.perf_counter(), require_tpu=False,
                            overrides=overrides, driver_hook=hook)


@pytest.mark.parametrize("cell", sorted(SMALL))
def test_sound_run_is_correct(cell, scratch_env):
    result = run(cell, scratch_env)
    assert result["correct"], result["checks"]
    assert {"setup_s", "infer_ms"} <= set(result["metrics"])
    json.dumps(result["checks"])


@pytest.mark.parametrize("cell", sorted(SMALL))
def test_control_is_not_correct(cell, scratch_env):
    result = run(cell, scratch_env, hook=controls_gated.install(DRIVER[cell]))
    assert not result["correct"], result["checks"]


def test_traced_run_reads_the_layout_counter(scratch_env):
    """On the CPU there are no device ops: the device-trace readers find
    nothing and stay silent, the layout counter reads."""
    result = run("pattern.infer", scratch_env, trace=True)
    assert result["correct"]
    assert set(result["metrics"]) == {"gated_pad_share"}
    assert 0.0 < result["metrics"]["gated_pad_share"]["value"] < 100.0
