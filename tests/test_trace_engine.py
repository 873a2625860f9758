"""Amortized trace-engine battery (DESIGN.md §13) + PR-5 satellite pins.

Load-bearing guarantees:

* **Parity battery** — the three engines over the shared factorization
  (numpy, sharded, and the jitted JAX engine with its MXU histogram)
  produce schedule quantities (vertex/edge/halo/cut counts, cache-hit
  data) **bit identical** to the per-capacity PR-4 ``np.unique`` reference
  (``GraphTrace.schedule_reference``) across every registered trace
  dataset x a power-of-two capacity sweep, including the >= 100k-edge
  acceptance operating point;
* **Capacity axis** — a batch of same-dataset trace scenarios differing
  only in ``tile_vertices`` evaluates in exactly ONE planner group, each
  row bit-identical to its lone evaluation;
* **Satellites** — canonical-JSON dataset cache keys (nested params no
  longer raise), byte-budget LRU on the resolved-trace cache, bounded
  per-trace schedule LRU, ``clear_trace_cache`` dropping per-trace
  schedules, vectorized ``cache_hit_fraction``, the streaming power-law
  generator's determinism/contract, the on-disk schedule cache round
  trip, and the ``trace_scale`` benchmark's drift gate.
"""

import json
import os

import numpy as np
import pytest

from repro.api import Scenario, evaluate_scenario, evaluate_scenarios
from repro.core import schedule_cache
from repro.core import trace as trace_mod
from repro.core.trace import (GraphTrace, clear_trace_cache,
                              register_trace_dataset, resolve_trace_dataset,
                              set_trace_cache_budget, trace_cache_info)
from repro.data import synthetic

#: Small deterministic parameters for every registered dataset.
DATASET_PARAMS = {
    "power_law": {"n_nodes": 1200, "n_edges": 9000, "seed": 1, "alpha": 1.5},
    "power_law_stream": {"n_nodes": 1200, "n_edges": 9000, "seed": 1,
                         "alpha": 1.5},
    "power_law_sharded": {"n_nodes": 1200, "n_edges": 9000, "seed": 1,
                          "alpha": 1.5},
    "cora": {},
    "molecule": {"batch": 16, "n_nodes": 12, "n_edges": 30},
    "ring_of_tiles": {"n_nodes": 512, "n_tiles": 8},
}

COUNT_FIELDS = ("vertex_counts", "edge_counts", "halo_counts",
                "remote_edge_counts")


def _pow2_caps(V):
    caps = sorted({max(1, V >> i) for i in range(1, 11, 2)} | {V})
    return caps


@pytest.fixture(autouse=True)
def _no_disk_cache(monkeypatch):
    """Unit tests never touch the user's on-disk cache by default."""
    monkeypatch.setenv("REPRO_TRACE_CACHE", "0")
    yield


# ---------------------------------------------------------------------------
# Parity battery: numpy / sharded / jax engines == PR-4 reference.
# ---------------------------------------------------------------------------
def _reference_trace(name, trace):
    """The trace to run the PR-4 oracle on: ``power_law_sharded`` builds
    factorization-only traces (no edge list -> no oracle), but its graph
    is by contract the same as ``power_law_stream`` for equal params."""
    if trace.has_edge_list:
        return trace
    assert name == "power_law_sharded"
    return resolve_trace_dataset("power_law_stream", DATASET_PARAMS[name])


@pytest.mark.parametrize("engine", ["numpy", "sharded"])
@pytest.mark.parametrize("name", sorted(DATASET_PARAMS))
def test_amortized_engine_bitmatches_reference(name, engine):
    trace = resolve_trace_dataset(name, DATASET_PARAMS[name])
    oracle = _reference_trace(name, trace)
    trace.clear_schedules()   # each engine computes; no LRU entry is read
    for cap in _pow2_caps(trace.n_nodes):
        new = trace.schedule(cap, engine=engine)
        ref = oracle.schedule_reference(cap)
        for f in COUNT_FIELDS:
            np.testing.assert_array_equal(
                getattr(new, f), getattr(ref, f),
                err_msg=f"{name} cap={cap} field={f}")
        for hdf in (0.0, 0.1, 1.0):
            np.testing.assert_array_equal(new.cache_hit_fraction(hdf),
                                          ref.cache_hit_fraction(hdf))
        assert new.halo_total == ref.halo_total
        assert new.cut_edges == ref.cut_edges


@pytest.mark.parametrize("sweep", [True, False], ids=["sweep", "one_by_one"])
@pytest.mark.parametrize("name", sorted(DATASET_PARAMS))
def test_jax_engine_bitmatches_reference(name, sweep):
    """A multi-capacity sweep (one factorization upload for all its
    capacities) and lone ``schedule`` calls both match the oracle and the
    numpy engine entry for entry."""
    trace = resolve_trace_dataset(name, DATASET_PARAMS[name])
    oracle = _reference_trace(name, trace)
    trace.clear_schedules()
    caps = _pow2_caps(trace.n_nodes)[:3]
    if sweep:
        scheds = trace.schedules(caps, engine="jax")
    else:
        scheds = [trace.schedule(cap, engine="jax") for cap in caps]
    trace.clear_schedules()
    host = trace.schedules(caps, engine="numpy")
    for cap, sched, want in zip(caps, scheds, host):
        ref = oracle.schedule_reference(cap)
        for f in COUNT_FIELDS:
            np.testing.assert_array_equal(
                getattr(sched, f), getattr(ref, f),
                err_msg=f"{name} cap={cap} field={f}")
            np.testing.assert_array_equal(
                getattr(sched, f), getattr(want, f),
                err_msg=f"{name} cap={cap} field={f} (numpy engine)")
        # disk-less schedules still answer cache-hit queries (lazy pairs)
        np.testing.assert_array_equal(sched.cache_hit_fraction(0.2),
                                      ref.cache_hit_fraction(0.2))


def _mxu_and_scatter(ids, halo, cut, n_tiles_pad):
    import jax
    import jax.numpy as jnp

    from repro.kernels import segment_reduce as sr

    ids, halo, cut = (jnp.asarray(a, jnp.int32) for a in (ids, halo, cut))
    mxu = jax.jit(lambda t, h, c: sr._mxu_histograms(
        t, h, c, jnp.max(c), n_tiles_pad))
    scatter = jax.jit(lambda t, h, c: sr._scatter_histograms(
        t, h, c, n_tiles_pad))
    return ([np.asarray(x) for x in mxu(ids, halo, cut)],
            [np.asarray(x) for x in scatter(ids, halo, cut)])


@pytest.mark.parametrize("weights", [(1,), (1, 255, 256, 65_537)],
                         ids=["simple", "multigraph"])
@pytest.mark.parametrize("n_tiles_pad", [1, 127, 128, 129, 677, 2392])
def test_mxu_histograms_bitmatch_bincount_and_scatter(n_tiles_pad, weights):
    """The two-level one-hot histogram (interpret mode here) is
    bit-identical to np.bincount and to the scatter-adds, on the one-plane
    branch (weights that fit a byte) and on the four-plane branch."""
    rng = np.random.default_rng(n_tiles_pad)
    u = 3000
    ids = rng.integers(0, n_tiles_pad, size=u)
    halo = rng.integers(0, 2, size=u)
    cut = np.where(rng.random(u) < 0.7, rng.choice(weights, size=u), 0)
    mxu, scatter = _mxu_and_scatter(ids, halo, cut, n_tiles_pad)
    want = (np.bincount(ids, halo, n_tiles_pad),
            np.bincount(ids, cut.astype(np.float64), n_tiles_pad))
    for got, ref, exact in zip(mxu, scatter, want):
        assert got.dtype == np.int32 and got.shape == (n_tiles_pad,)
        np.testing.assert_array_equal(got, exact)
        np.testing.assert_array_equal(got, ref)


def test_mxu_histogram_exact_past_2p24_across_blocks():
    """One tile's cut total passes 2^24 over several edge blocks: each
    block's float32 partial stays exact and the blocks add in int32."""
    from repro.kernels import segment_reduce as sr

    block = sr._MXU_BLOCK_EDGES
    n_tiles_pad, u = 300, 4 * block
    ids = np.arange(u) % n_tiles_pad
    ids[::256] = 7                        # tile 7 gets edges in every block
    halo = np.ones(u, np.int64)
    cut = np.where(ids == 7, 65_537, 1)
    total = int(cut[ids == 7].sum())
    per_block = [int(cut[b:b + block][ids[b:b + block] == 7].sum())
                 for b in range(0, u, block)]
    assert total > 2**24 and max(per_block) < 2**24
    mxu, scatter = _mxu_and_scatter(ids, halo, cut, n_tiles_pad)
    assert int(mxu[1][7]) == total
    for got, ref in zip(mxu, scatter):
        np.testing.assert_array_equal(got, ref)
    np.testing.assert_array_equal(mxu[0], np.bincount(ids, minlength=300))
    np.testing.assert_array_equal(
        mxu[1], np.bincount(ids, cut.astype(np.float64), 300))


def test_big_power_law_reference_parity_and_bruteforce():
    """The >= 100k-edge acceptance point, rerun through the new engine."""
    params = {"n_nodes": 20000.0, "n_edges": 120000.0, "seed": 0.0,
              "alpha": 1.3}
    trace = resolve_trace_dataset("power_law", params)
    assert trace.n_edges >= 100_000
    sched = trace.schedule(1024)
    ref = trace.schedule_reference(1024)
    for f in COUNT_FIELDS:
        np.testing.assert_array_equal(getattr(sched, f), getattr(ref, f))
    # Brute-force np.unique halo on a few tiles (full check lives in
    # test_trace.py and runs against this same engine).
    K = sched.K
    dst_tile = trace.receivers // K
    for t in (0, sched.n_tiles // 2, sched.n_tiles - 1):
        srcs = trace.senders[dst_tile == t]
        remote = srcs[(srcs // K) != t]
        assert sched.halo_counts[t] == np.unique(remote).size


def test_engine_name_validated():
    trace = resolve_trace_dataset("ring_of_tiles",
                                  {"n_nodes": 64, "n_tiles": 4})
    with pytest.raises(ValueError, match="engine"):
        trace.schedule(16, engine="bogus")
    with pytest.raises(ValueError, match="engine"):
        trace.schedules([16], engine="bogus")


# ---------------------------------------------------------------------------
# Capacity axis: one planner group per (dataflow, dataset), exact rows.
# ---------------------------------------------------------------------------
def test_capacity_sweep_is_one_planner_group():
    params = {"n_nodes": 1500.0, "n_edges": 9000.0, "seed": 0.0,
              "alpha": 1.4}
    caps = (64.0, 128.0, 300.0, 750.0, 1500.0)
    batch = [Scenario.trace("engn", dataset="power_law", params=params,
                            N=30.0, T=5.0, tile_vertices=c) for c in caps]
    res = evaluate_scenarios(batch)
    # THE acceptance assertion: same dataset, capacities only -> 1 group.
    assert res.n_evaluations == 1
    assert len({s.plan_key() for s in batch}) == 1
    for s, r in zip(batch, res.results):
        lone = evaluate_scenario(s)
        assert r.total_bits == lone.total_bits
        assert r.total_iterations == lone.total_iterations
        assert r.breakdown == lone.breakdown
        assert r.iteration_breakdown == lone.iteration_breakdown
        assert r.n_tiles == lone.n_tiles
    # n_tiles must reflect each row's own capacity
    assert [r.n_tiles for r in res.results] == \
        [float(-(-1500 // int(c))) for c in caps]


def test_capacity_axis_with_widths_and_hardware_overrides():
    params = {"n_nodes": 900.0, "n_edges": 5000.0, "seed": 3.0}
    batch = [
        Scenario.trace("hygcn", dataset="power_law", params=params,
                       N=32.0, T=8.0, tile_vertices=cap,
                       widths=(32.0, 16.0, 8.0), hardware={"B": B})
        for cap, B in ((100.0, 1000.0), (450.0, 2000.0), (900.0, 1000.0))
    ]
    res = evaluate_scenarios(batch)
    assert res.n_evaluations == 1
    for s, r in zip(batch, res.results):
        lone = evaluate_scenario(s)
        assert r.total_bits == lone.total_bits
        assert r.breakdown == lone.breakdown


# ---------------------------------------------------------------------------
# Satellite: canonical-JSON cache keys (nested params used to raise).
# ---------------------------------------------------------------------------
def test_cache_key_canonicalizes_nested_params():
    built = []

    def builder(**params):
        built.append(params)
        return GraphTrace(np.array([0, 1]), np.array([1, 0]), 2)

    register_trace_dataset("_nested_params_ds", builder, overwrite=True)
    try:
        nested = {"shape": {"n": 2.0, "m": [1, 2]}, "seed": 0}
        # PR-4's tuple(sorted(...)) key raised TypeError on dict values.
        t1 = resolve_trace_dataset("_nested_params_ds", nested)
        t2 = resolve_trace_dataset(
            "_nested_params_ds",
            {"seed": 0, "shape": {"m": [1, 2], "n": 2.0}})
        assert t1 is t2  # key order canonicalized -> one build
        assert len(built) == 1
        t3 = resolve_trace_dataset("_nested_params_ds",
                                   {"shape": {"n": 3.0, "m": [1, 2]},
                                    "seed": 0})
        assert t3 is not t1 and len(built) == 2
        # numpy scalars canonicalize like their Python values
        t4 = resolve_trace_dataset("_nested_params_ds",
                                   {"shape": {"n": np.float64(2.0),
                                              "m": [1, 2]},
                                    "seed": np.int64(0)})
        assert t4 is t1 and len(built) == 2
        # integer-valued floats merge with ints (the front door
        # normalizes params to floats; direct callers pass ints — both
        # must share one cache/disk entry, like the old tuple key did)
        t5 = resolve_trace_dataset("_nested_params_ds",
                                   {"shape": {"n": 2, "m": [1.0, 2.0]},
                                    "seed": 0.0})
        assert t5 is t1 and len(built) == 2
        assert (trace_mod._canonical_params({"n": 1000000})
                == trace_mod._canonical_params({"n": 1000000.0}))
    finally:
        trace_mod._TRACE_DATASETS.pop("_nested_params_ds", None)
        clear_trace_cache()


# ---------------------------------------------------------------------------
# Satellite: bounded caches.
# ---------------------------------------------------------------------------
def test_trace_cache_byte_budget_evicts_lru():
    clear_trace_cache()
    old_budget = trace_cache_info()["budget_bytes"]
    try:
        a = resolve_trace_dataset("ring_of_tiles",
                                  {"n_nodes": 256, "n_tiles": 4})
        set_trace_cache_budget(max(1, a.nbytes // 2))
        # the most recent entry always survives, even over budget
        assert trace_cache_info()["entries"] == 1
        b = resolve_trace_dataset("ring_of_tiles",
                                  {"n_nodes": 512, "n_tiles": 4})
        info = trace_cache_info()
        assert info["entries"] == 1
        assert resolve_trace_dataset("ring_of_tiles",
                                     {"n_nodes": 512, "n_tiles": 4}) is b
        # raising the budget keeps both
        set_trace_cache_budget(10 * (a.nbytes + b.nbytes))
        resolve_trace_dataset("ring_of_tiles", {"n_nodes": 256, "n_tiles": 4})
        assert trace_cache_info()["entries"] == 2
        with pytest.raises(ValueError, match=">= 0"):
            set_trace_cache_budget(-1)
    finally:
        set_trace_cache_budget(old_budget)
        clear_trace_cache()


def test_per_trace_schedule_lru_bounded(monkeypatch):
    monkeypatch.setattr(GraphTrace, "schedule_cache_entries", 4)
    trace = resolve_trace_dataset("power_law",
                                  {"n_nodes": 600, "n_edges": 3000,
                                   "seed": 0})
    trace.clear_schedules()
    caps = [10, 20, 30, 40, 50, 60]
    for c in caps:
        trace.schedule(c)
    assert len(trace._schedules) == 4
    assert list(trace._schedules) == caps[-4:]
    # an LRU hit refreshes recency
    trace.schedule(30)
    trace.schedule(70)
    assert 30 in trace._schedules and 40 not in trace._schedules


def test_schedules_sweep_wider_than_lru_returns_everything(monkeypatch):
    """A capacity sweep larger than the schedule LRU must still return a
    full schedule per requested capacity (regression: eviction during
    the batch used to surface None entries)."""
    monkeypatch.setattr(GraphTrace, "schedule_cache_entries", 4)
    trace = resolve_trace_dataset("power_law",
                                  {"n_nodes": 600, "n_edges": 3000,
                                   "seed": 6})
    trace.clear_schedules()
    caps = list(range(10, 100, 10))  # 9 distinct > LRU limit of 4
    scheds = trace.schedules(caps)
    assert len(scheds) == len(caps)
    for cap, s in zip(caps, scheds):
        assert s is not None and s.capacity == cap
        ref = trace.schedule_reference(cap)
        np.testing.assert_array_equal(s.halo_counts, ref.halo_counts)
    assert len(trace._schedules) == 4


def test_clear_trace_cache_drops_per_trace_schedules():
    trace = resolve_trace_dataset("power_law",
                                  {"n_nodes": 500, "n_edges": 2500,
                                   "seed": 4})
    trace.schedule(100)
    assert trace._schedules
    clear_trace_cache()
    assert not trace._schedules
    assert trace_cache_info()["entries"] == 0


# ---------------------------------------------------------------------------
# Satellite: vectorized cache_hit_fraction.
# ---------------------------------------------------------------------------
def test_cache_hit_fraction_vectorizes_over_hdf():
    trace = resolve_trace_dataset("power_law",
                                  {"n_nodes": 2000, "n_edges": 16000,
                                   "seed": 2, "alpha": 1.2})
    sched = trace.schedule(512)
    hdf = np.array([0.0, 0.05, 0.1, 0.5, 1.0])
    vec = sched.cache_hit_fraction(hdf)
    assert vec.shape == (5, sched.n_tiles)
    for i, h in enumerate(hdf):
        np.testing.assert_array_equal(vec[i],
                                      sched.cache_hit_fraction(float(h)))
    grid = sched.cache_hit_fraction(hdf.reshape(5, 1))
    assert grid.shape == (5, 1, sched.n_tiles)
    # monotone in the cache size, bounded in [0, 1]
    assert np.all(np.diff(vec, axis=0) >= 0)
    assert np.all((vec >= 0) & (vec <= 1))
    for bad in (1.5, -0.1, float("nan"), np.array([0.1, 2.0])):
        with pytest.raises(ValueError, match="high_degree_fraction"):
            sched.cache_hit_fraction(bad)


# ---------------------------------------------------------------------------
# Satellite: streaming chunked power-law generator.
# ---------------------------------------------------------------------------
def test_power_law_edges_contract():
    snd, rcv = synthetic.power_law_edges(7, n_nodes=5000, n_edges=30000)
    assert snd.dtype == np.int32 and rcv.dtype == np.int32
    assert snd.size == rcv.size == 30000
    assert not np.any(snd == rcv)
    assert snd.min() >= 0 and rcv.max() < 5000
    # deterministic in (seed, params)
    snd2, rcv2 = synthetic.power_law_edges(7, n_nodes=5000, n_edges=30000)
    np.testing.assert_array_equal(snd, snd2)
    np.testing.assert_array_equal(rcv, rcv2)
    # the stream yields the same edges chunk by chunk
    parts = list(synthetic.power_law_edge_stream(7, n_nodes=5000,
                                                 n_edges=30000))
    np.testing.assert_array_equal(np.concatenate([p[0] for p in parts]), snd)
    np.testing.assert_array_equal(np.concatenate([p[1] for p in parts]), rcv)
    # chunked consumption is part of the stream identity: edge counts
    # that straddle chunk boundaries still come out exact
    chunks = list(synthetic.power_law_edge_stream(0, n_nodes=100,
                                                  n_edges=2500,
                                                  chunk_edges=1000))
    assert [c[0].size for c in chunks] == [1000, 1000, 500]
    with pytest.raises(ValueError, match="n_nodes >= 2"):
        list(synthetic.power_law_edge_stream(0, n_nodes=1, n_edges=5))
    with pytest.raises(ValueError, match="chunk_edges"):
        list(synthetic.power_law_edge_stream(0, n_nodes=10, n_edges=5,
                                             chunk_edges=0))
    # power-law shape: destination degrees are heavy-tailed
    degs = np.bincount(rcv, minlength=5000)
    assert degs.max() > 20 * max(1.0, degs.mean())


def test_power_law_stream_dataset_registered():
    trace = resolve_trace_dataset("power_law_stream",
                                  {"n_nodes": 800, "n_edges": 4000,
                                   "seed": 5, "alpha": 1.3})
    assert (trace.n_nodes, trace.n_edges) == (800, 4000)
    s = evaluate_scenario(Scenario.trace(
        "engn", dataset="power_law_stream",
        params={"n_nodes": 800.0, "n_edges": 4000.0, "seed": 5.0,
                "alpha": 1.3},
        N=30.0, T=5.0, tile_vertices=200.0))
    assert np.isfinite(s.total_bits) and s.total_bits > 0


# ---------------------------------------------------------------------------
# Satellite: content-addressed on-disk cache.
# ---------------------------------------------------------------------------
def test_disk_cache_round_trip(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_TRACE_CACHE", str(tmp_path))
    monkeypatch.setenv("REPRO_TRACE_CACHE_MIN_EDGES", "0")
    params = {"n_nodes": 700, "n_edges": 4200, "seed": 9, "alpha": 1.4}
    clear_trace_cache()
    t1 = resolve_trace_dataset("power_law", params)
    s1 = t1.schedule(128)
    # format v2: one graph part-directory + one schedule npz
    assert len(list(tmp_path.rglob("*.graph"))) == 1
    assert len(list(tmp_path.rglob("*.npz"))) == 1
    clear_trace_cache()
    t2 = resolve_trace_dataset("power_law", params)
    assert t2 is not t1
    np.testing.assert_array_equal(t2.senders, t1.senders)
    np.testing.assert_array_equal(t2.receivers, t1.receivers)
    np.testing.assert_array_equal(t2.row_ptr, t1.row_ptr)
    # schedule comes from disk (counts) and still answers cache-hit
    # queries through the lazily rebuilt pair provider
    s2 = t2.schedule(128)
    for f in COUNT_FIELDS:
        np.testing.assert_array_equal(getattr(s2, f), getattr(s1, f))
    np.testing.assert_array_equal(s2.cache_hit_fraction(0.1),
                                  s1.cache_hit_fraction(0.1))
    ref = t2.schedule_reference(128)
    for f in COUNT_FIELDS:
        np.testing.assert_array_equal(getattr(s2, f), getattr(ref, f))
    clear_trace_cache()


def test_disk_cache_disabled_and_tokenless(tmp_path, monkeypatch):
    params = {"n_nodes": 400, "n_edges": 2000, "seed": 1}
    monkeypatch.setenv("REPRO_TRACE_CACHE", "0")
    monkeypatch.setenv("REPRO_TRACE_CACHE_MIN_EDGES", "0")
    clear_trace_cache()
    resolve_trace_dataset("power_law", params).schedule(64)
    assert schedule_cache.cache_root() is None
    # tokenless datasets (ring_of_tiles, ad-hoc registrations) never
    # write disk entries even when the cache is on
    monkeypatch.setenv("REPRO_TRACE_CACHE", str(tmp_path))
    clear_trace_cache()
    resolve_trace_dataset("ring_of_tiles",
                          {"n_nodes": 400, "n_tiles": 4}).schedule(64)
    assert list(tmp_path.rglob("*.npz")) == []
    assert list(tmp_path.rglob("*.graph")) == []
    clear_trace_cache()


def test_disk_cache_min_edges_threshold(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_TRACE_CACHE", str(tmp_path))
    monkeypatch.setenv("REPRO_TRACE_CACHE_MIN_EDGES", "5000")
    clear_trace_cache()
    resolve_trace_dataset("power_law",
                          {"n_nodes": 300, "n_edges": 1000,
                           "seed": 0}).schedule(64)
    # below the threshold: no graph dirs, no schedule npz
    assert list(tmp_path.rglob("*.npz")) == []
    assert list(tmp_path.rglob("*.graph")) == []
    clear_trace_cache()


def test_disk_cache_corrupt_entry_is_a_miss(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_TRACE_CACHE", str(tmp_path))
    monkeypatch.setenv("REPRO_TRACE_CACHE_MIN_EDGES", "0")
    params = {"n_nodes": 500, "n_edges": 2500, "seed": 2}
    clear_trace_cache()
    t1 = resolve_trace_dataset("power_law", params)
    for f in tmp_path.rglob("*.npz"):
        f.write_bytes(b"not an npz")
    for f in tmp_path.rglob("*.graph/*"):
        f.write_bytes(b"garbage")  # torn npy parts AND torn meta.json
    clear_trace_cache()
    t2 = resolve_trace_dataset("power_law", params)  # rebuilds, no raise
    np.testing.assert_array_equal(t2.senders, t1.senders)
    # the damaged graph directory was dropped and re-stored clean
    clear_trace_cache()
    t3 = resolve_trace_dataset("power_law", params)
    np.testing.assert_array_equal(np.asarray(t3.row_ptr), t1.row_ptr)
    clear_trace_cache()


# ---------------------------------------------------------------------------
# CI gate: the trace_scale benchmark's drift check.
# ---------------------------------------------------------------------------
def test_trace_scale_benchmark_smoke(tmp_path):
    from benchmarks import trace_scale

    out = tmp_path / "bench.json"
    rc = trace_scale.main(["--edges", "20000,50000", "--points", "6",
                           "--json", str(out)])
    assert rc == 0
    payload = json.loads(out.read_text())
    assert payload["benchmark"] == "trace_scale"
    assert payload["drift_failures"] == []
    for row in payload["rows"]:
        assert row["drift_errors"] == []
        assert row["edges_per_sec"] > 0
        assert row["speedup_vs_reference"] is not None
        assert row["n_capacities"] == len(row["capacities"]) == 6
        # PR-6 sharded-pipeline stages + peak-RSS tracking per row
        assert row["t_total_sharded_s"] > 0
        assert row["t_total_single_s"] > 0
        assert row["n_shards"] >= 1
        assert row["rss_peak_kb"]["shard_generate_sort_kb"] != 0


@pytest.mark.slow
def test_trace_scale_ten_million_edges_end_to_end(tmp_path):
    """The 10^7-edge sweep (amortized engine only) schedules on CPU."""
    from benchmarks import trace_scale

    out = tmp_path / "bench.json"
    rc = trace_scale.main(["--edges", "10000000", "--ref-max-edges", "0",
                           "--json", str(out)])
    assert rc == 0
    payload = json.loads(out.read_text())
    row = payload["rows"][0]
    assert row["n_edges"] == 10_000_000
    assert row["drift_errors"] == []
    assert row["edges_per_sec"] > 1e6


# ---------------------------------------------------------------------------
# PR-8 satellite: float64-exactness at the 2^53 boundary.  The model
# auditor (repro.analysis) proves the *closed forms* stay exactly
# representable at the ROADMAP envelope; this pins the same property for
# the trace engine's integer pipeline: multiplicity prefix sums and
# schedule counts at 2^53-adjacent edge totals must match a Python-int
# oracle exactly (int64 end to end, no float64 round-trip losses).
# ---------------------------------------------------------------------------

def _python_int_schedule_oracle(u_snd, u_rcv, mult, V, cap):
    """Schedule counts re-derived with arbitrary-precision Python ints."""
    n_tiles = -(-V // cap)
    edge = [0] * n_tiles
    remote = [0] * n_tiles
    halo_sources = [set() for _ in range(n_tiles)]
    for s, r, m in zip(u_snd, u_rcv, mult):
        t = int(r) // cap
        edge[t] += int(m)
        if int(s) // cap != t:
            remote[t] += int(m)
            halo_sources[t].add(int(s))
    return edge, remote, [len(h) for h in halo_sources]


def _dense_pairs(V, seed):
    """A deterministic sender-major unique-pair set over V vertices."""
    rng = np.random.default_rng(seed)
    keys = np.unique(rng.integers(0, V * V, size=4 * V))
    return (keys // V).astype(np.int64), (keys % V).astype(np.int64)


def test_schedule_oracle_convention_matches_engine():
    """Validate the Python-int oracle's tile convention at small scale."""
    V, cap = 96, 32
    u_snd, u_rcv = _dense_pairs(V, seed=7)
    mult = (1 + (u_snd + u_rcv) % 5).astype(np.int64)
    prefix = np.zeros(mult.size + 1, dtype=np.int64)
    np.cumsum(mult, out=prefix[1:])
    trace = GraphTrace.from_factorization(V, u_snd, u_rcv, prefix)
    sched = trace.schedule(cap)
    edge, remote, halo = _python_int_schedule_oracle(
        u_snd, u_rcv, mult, V, cap)
    assert [int(x) for x in sched.edge_counts] == edge
    assert [int(x) for x in sched.remote_edge_counts] == remote
    assert [int(x) for x in sched.halo_counts] == halo


@pytest.mark.parametrize("total", [2**53 - 1, 2**53 + 4097, 10**8 + 7],
                         ids=["2p53-1", "2p53+4097", "1e8"])
def test_schedule_counts_exact_at_2p53_boundary(total):
    """2^53-adjacent multiplicity totals survive the int64 pipeline.

    One unique pair carries nearly the whole edge multiplicity, so prefix
    sums and per-tile totals land at or past 2^53 (where float64 spacing
    is 2.0).  The int64 side — E, CSR row pointers, out-degrees — must
    equal the Python-int oracle *exactly* at any scale; a weighted
    float64 bincount anywhere in the multiplicity path shows up here as
    an off-by-a-few (the pre-PR-8 behavior).  The float64-stored schedule
    counts must be exact up to 2^53 and nearest-representable — one final
    rounding, never accumulated error — beyond it.
    """
    V, cap = 96, 32
    u_snd, u_rcv = _dense_pairs(V, seed=11)
    U = u_snd.size
    mult = np.ones(U, dtype=np.int64)
    mult[U // 3] = total - (U - 1)  # a 2^53-scale hot pair
    prefix = np.zeros(U + 1, dtype=np.int64)
    np.cumsum(mult, out=prefix[1:])
    assert prefix.dtype == np.int64 and int(prefix[-1]) == total

    trace = GraphTrace.from_factorization(V, u_snd, u_rcv, prefix)
    assert trace.n_edges == total  # no float64 narrowing of E
    edge, remote, halo = _python_int_schedule_oracle(
        u_snd, u_rcv, mult, V, cap)

    # int64 pipeline: exact at any scale.
    assert trace.row_ptr.dtype == np.int64
    assert int(trace.row_ptr[-1]) == total
    row_counts = [0] * V
    out_deg = [0] * V
    for s, r, m in zip(u_snd, u_rcv, mult):
        row_counts[int(r)] += int(m)
        out_deg[int(s)] += int(m)
    assert [int(x) for x in np.diff(trace.row_ptr)] == row_counts
    assert [int(x) for x in trace.out_degrees()] == out_deg

    # float64-stored schedule counts: exact <= 2^53, one nearest-
    # representable rounding beyond (never accumulated error).
    sched = trace.schedule(cap)
    assert list(sched.edge_counts) == [float(x) for x in edge]
    assert list(sched.remote_edge_counts) == [float(x) for x in remote]
    assert [int(x) for x in sched.halo_counts] == halo
    if total <= 2**53:
        assert [int(x) for x in sched.edge_counts] == edge
        assert [int(x) for x in sched.remote_edge_counts] == remote
        assert sched.cut_edges == sum(remote)
    assert sched.halo_total == sum(halo)


@pytest.mark.parametrize("n_shards", [1, 2, 8])
def test_sharded_counts_exact_at_2p53_boundary(n_shards, monkeypatch):
    """The sharded engine's chunks accumulate cut edges in int64 like the
    single-host pass: a 2^53-scale hot pair, wherever it sits and however
    the factorization is split, gives the exact per-tile counts (and the
    schedule one final float64 rounding of them)."""
    from repro.distributed import trace_shard

    V, cap, total = 96, 32, 2**53 + 4097
    u_snd, u_rcv = _dense_pairs(V, seed=11)
    U = u_snd.size
    monkeypatch.setenv("REPRO_TRACE_SHARDS", str(n_shards))
    for hot in (U // 4, U // 3, U // 2):
        mult = np.ones(U, dtype=np.int64)
        mult[hot] = total - (U - 1)
        prefix = np.zeros(U + 1, dtype=np.int64)
        np.cumsum(mult, out=prefix[1:])
        trace = GraphTrace.from_factorization(V, u_snd, u_rcv, prefix)
        _, remote, halo = _python_int_schedule_oracle(
            u_snd, u_rcv, mult, V, cap)
        got_halo, got_remote = trace_shard.sharded_schedule_counts(
            trace._pair_factorization(), cap, -(-V // cap),
            n_shards=n_shards)
        assert [int(x) for x in got_remote] == remote, hot
        assert [int(x) for x in got_halo] == halo, hot
        sched = trace.schedule(cap, engine="sharded")
        assert list(sched.remote_edge_counts) == [float(x) for x in remote]
        assert [int(x) for x in sched.halo_counts] == halo


def test_schedule_kernel_imports_no_analytical_core():
    """The device kernel reads and writes only the leaf counter module:
    importing it loads neither ``repro.core`` nor ``repro.core.trace``."""
    import subprocess
    import sys
    from pathlib import Path

    src = str(Path(__file__).resolve().parents[1] / "src")
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": os.pathsep.join([src,
                                          os.environ.get("PYTHONPATH", "")])}
    code = ("import sys, repro.kernels.segment_reduce; "
            "print(sorted(m for m in ('repro.core', 'repro.core.trace') "
            "if m in sys.modules))")
    out = subprocess.run([sys.executable, "-c", code], env=env, check=True,
                         capture_output=True, text=True)
    assert out.stdout.strip() == "[]", out.stdout + out.stderr


# ---------------------------------------------------------------------------
# Schedule-engine spans and counters: host time per step and bytes uploaded,
# in trace_cache_info()["stats"] and on the profiler's host timeline.
# ---------------------------------------------------------------------------

_DEVICE_SPAN_COUNTERS = ("schedule_upload_ns", "schedule_dispatch_ns",
                         "schedule_wait_ns")


def _simple_trace(V=96, seed=3):
    """A graph with one edge per (sender, receiver) pair, so U = E."""
    u_snd, u_rcv = _dense_pairs(V, seed)
    trace = GraphTrace(u_snd, u_rcv, V)
    assert trace.n_edges == u_snd.size
    return trace


@pytest.mark.parametrize("engine", ["jax", "numpy"])
def test_schedule_spans_and_upload_counters(engine):
    trace = _simple_trace()
    U, caps = trace.n_edges, [8, 16, 32]
    trace._pair_factorization()           # the sort is not a span's work
    before = dict(trace_cache_info()["stats"])
    trace.schedules(caps, engine=engine)
    after = trace_cache_info()["stats"]
    d = {k: after[k] - before[k] for k in after}
    on_device = engine == "jax"
    # First call on the trace: int32 senders, receivers and multiplicities
    # and the bool new-sender mask (13 B per pair); per capacity the int32
    # stride.
    assert d["schedule_upload_bytes"] == on_device * (13 * U + 4 * len(caps))
    assert d["schedule_factorization_uploads"] == on_device
    assert d["schedule_factorization_reuses"] == 0
    for key in _DEVICE_SPAN_COUNTERS:
        assert (d[key] > 0) == on_device, key
    assert d["schedules_ns"] >= sum(d[k] for k in _DEVICE_SPAN_COUNTERS)
    assert d["schedules_ns"] > 0
    # 96 vertices: every capacity's tile count is on the MXU side.
    assert d["schedule_hist_mxu"] == on_device * len(caps)
    assert d["schedule_hist_scatter"] == 0
    # A second call on the same trace finds the factorization resident:
    # only the strides move.
    trace.clear_schedules()
    _, d = _stat_delta(lambda: trace.schedules(caps, engine=engine))
    assert d["schedule_upload_bytes"] == on_device * 4 * len(caps)
    assert d["schedule_factorization_uploads"] == 0
    assert d["schedule_factorization_reuses"] == on_device
    assert d["schedule_computes"] == len(caps)


@pytest.mark.parametrize("above", [False, True], ids=["at_T", "above_T"])
def test_schedule_hist_counters_follow_the_crossover(above):
    """One bump per capacity, on the side of the crossover T that the
    static padded tile count lies on; the result is exact either way."""
    from repro.kernels import segment_reduce as sr

    n_tiles_pad = sr.MXU_HIST_MAX_TILES + above
    u_snd = np.array([0, 0, 5, 9], np.int32)
    u_rcv = np.array([3, 70, 1, 9], np.int32)
    new_src = np.array([True, False, True, True])
    mult = np.array([2, 1, 300, 1], np.int32)
    before = dict(trace_cache_info()["stats"])
    halo, cut = sr.schedule_counts(u_snd, u_rcv, new_src, mult, 4,
                                   n_tiles_pad)
    after = trace_cache_info()["stats"]
    assert after["schedule_hist_mxu"] - before["schedule_hist_mxu"] == (
        not above)
    assert after["schedule_hist_scatter"] - before["schedule_hist_scatter"] \
        == above
    want_halo = np.zeros(n_tiles_pad, np.int64)
    want_cut = np.zeros(n_tiles_pad, np.int64)
    want_halo[[0, 17]] = 1                # pairs (0, 3) and (5, 1) are remote
    want_cut[[0, 17]] = [300, 1]
    np.testing.assert_array_equal(np.asarray(halo), want_halo)
    np.testing.assert_array_equal(np.asarray(cut), want_cut)


def test_schedule_spans_nest_on_the_profiler_timeline(tmp_path):
    import glob

    import jax
    from jax.profiler import ProfileData

    trace = _simple_trace()
    # Compile outside the profile; the factorization stays resident.
    trace.schedules([8], engine="jax")
    trace.clear_schedules()
    jax.profiler.start_trace(str(tmp_path))
    try:
        trace.schedules([8, 16], engine="jax")
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
             for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name.startswith("repro.trace.")]
    outer = [s for s in spans if s[0] == "repro.trace.schedules"]
    inner = [s for s in spans if s[0].startswith("repro.trace.schedule.")]
    assert len(outer) == 1
    # No upload of the resident factorization; per capacity the stride's
    # upload, the dispatch and the wait.
    assert sorted(s[0] for s in inner) == sorted(
        2 * ["repro.trace.schedule.upload", "repro.trace.schedule.dispatch",
             "repro.trace.schedule.wait"])
    _, lo, hi = outer[0]
    assert all(lo <= start and end <= hi for _, start, end in inner)


def _stat_delta(fn):
    before = dict(trace_cache_info()["stats"])
    out = fn()
    after = trace_cache_info()["stats"]
    return out, {k: after[k] - before[k] for k in after}


@pytest.mark.parametrize("call", ["sweep", "single"])
def test_factorization_uploads_once_per_call(call):
    """The device engine moves the pair factorization at most once per
    call, and only on the trace's first call: every capacity of that call
    and every later call is served from the resident copy."""
    trace = _simple_trace()
    trace._pair_factorization()
    caps = [8, 16, 32] if call == "sweep" else [8]

    def run():
        trace.clear_schedules()
        if call == "sweep":
            return trace.schedules(caps, engine="jax")
        return trace.schedule(caps[0], engine="jax")

    for uploads in (1, 0):
        _, d = _stat_delta(run)
        assert d["schedule_factorization_uploads"] == uploads
        assert d["schedule_factorization_reuses"] == 1 - uploads
        assert d["schedule_computes"] == len(caps)
    trace.clear_schedules()
    _, d = _stat_delta(lambda: trace.schedules(caps, engine="numpy"))
    assert d["schedule_factorization_uploads"] == 0
    assert d["schedule_factorization_reuses"] == 0


@pytest.mark.parametrize("above", [False, True], ids=["mxu", "scatter"])
def test_schedule_counts_on_device_arrays_moves_only_the_stride(above):
    """Given the device arrays of ``upload_factorization``,
    ``schedule_counts`` uploads only ``K`` (4 B) and answers exactly as
    it does from the host arrays, on either histogram path."""
    from repro.kernels import segment_reduce as sr

    n_tiles_pad = sr.MXU_HIST_MAX_TILES + 1 if above else 24
    trace = _simple_trace()
    u_snd, u_rcv, u_new_src, mp = trace._pair_factorization()
    mult = np.diff(mp)
    fact, d = _stat_delta(
        lambda: sr.upload_factorization(u_snd, u_rcv, u_new_src, mult))
    assert d["schedule_upload_bytes"] == 13 * trace.n_edges
    assert d["schedule_factorization_uploads"] == 1
    for K in (4, 12):
        dev, d = _stat_delta(lambda: sr.schedule_counts(*fact, K, n_tiles_pad))
        assert d["schedule_upload_bytes"] == 4
        assert d["schedule_factorization_uploads"] == 0
        host, d = _stat_delta(lambda: sr.schedule_counts(
            u_snd, u_rcv, u_new_src, mult, K, n_tiles_pad))
        assert d["schedule_upload_bytes"] == 13 * trace.n_edges + 4
        assert d["schedule_factorization_uploads"] == 1
        for got, want in zip(dev, host):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# The resident factorization: one per process, the most recently used
# trace's, released with its trace.
# ---------------------------------------------------------------------------

def _resident_is(trace) -> bool:
    slot = trace_mod._RESIDENT
    return slot is not None and slot[0]() is trace._pair_factorization()[3]


@pytest.mark.parametrize("path", ["mxu", "scatter"])
def test_second_sweep_reuses_the_resident_factorization(path):
    """A second sweep on a trace uploads only its strides, bumps
    ``schedule_factorization_reuses`` once, still computes every capacity,
    and equals the numpy engine entry for entry on either histogram
    path."""
    from repro.kernels import segment_reduce as sr

    if path == "mxu":
        trace, caps = _simple_trace(), [8, 16, 32]
    else:
        V = sr.MXU_HIST_MAX_TILES + 100
        rng = np.random.default_rng(11)
        trace = GraphTrace(rng.integers(0, V, 3000), rng.integers(0, V, 3000),
                           V)
        caps = [1, 4096]           # V padded tiles: the scatter-adds
    trace.schedules(caps, engine="jax")
    assert _resident_is(trace)
    trace.clear_schedules()
    got, d = _stat_delta(lambda: trace.schedules(caps, engine="jax"))
    assert d["schedule_upload_bytes"] == 4 * len(caps)
    assert d["schedule_factorization_uploads"] == 0
    assert d["schedule_factorization_reuses"] == 1
    assert d["schedule_computes"] == len(caps)
    assert d[f"schedule_hist_{path}"] == len(caps)
    assert d["schedule_cache_hits"] == d["schedule_disk_hits"] == 0
    trace.clear_schedules()
    want = trace.schedules(caps, engine="numpy")
    for cap, a, b in zip(caps, got, want):
        for f in COUNT_FIELDS:
            np.testing.assert_array_equal(getattr(a, f), getattr(b, f),
                                          err_msg=f"cap={cap} field={f}")


@pytest.mark.parametrize("how", ["other_trace", "clear_trace_cache",
                                 "evicted", "collected"])
def test_resident_factorization_is_released(how):
    """At most one factorization stays on the device: a call on another
    trace replaces it (the first trace's next call uploads again), and
    ``clear_trace_cache``, eviction from the resolved-trace cache and
    garbage collection of its trace release it."""
    import gc

    clear_trace_cache()
    old_budget = trace_cache_info()["budget_bytes"]
    params = {"n_nodes": 300, "n_edges": 1500}
    try:
        first = resolve_trace_dataset("power_law", {**params, "seed": 1})
        first.schedules([16], engine="jax")
        assert _resident_is(first)
        if how == "other_trace":
            other = _simple_trace()
            other.schedules([16], engine="jax")
            assert _resident_is(other) and not _resident_is(first)
            first.clear_schedules()
            _, d = _stat_delta(lambda: first.schedules([16], engine="jax"))
            assert d["schedule_factorization_uploads"] == 1
            assert d["schedule_factorization_reuses"] == 0
            assert _resident_is(first)
        else:
            if how == "clear_trace_cache":
                clear_trace_cache()
            elif how == "evicted":
                set_trace_cache_budget(1)  # the newest entry alone survives
                resolve_trace_dataset("power_law", {**params, "seed": 2})
                assert trace_cache_info()["entries"] == 1
            else:
                lone = _simple_trace()     # held by nothing but this frame
                lone.schedules([16], engine="jax")
                assert _resident_is(lone)
                del lone
                gc.collect()
            assert trace_mod._RESIDENT is None
    finally:
        set_trace_cache_budget(old_budget)
        clear_trace_cache()
