import numpy as np
import pytest

import graphgen
import reference


@pytest.mark.parametrize("n, m, a", [(2708, 5278, 0.6), (50_000, 400_000, 0.45)])
def test_exact_sizes_simple_and_symmetric(n, m, a):
    snd, rcv = graphgen.simple_symmetric_graph(n, m, a, seed=3)
    assert snd.dtype == rcv.dtype == np.int32
    assert snd.size == rcv.size == 2 * m
    assert snd.min() >= 0 and rcv.max() < n
    assert not np.any(snd == rcv)
    key = snd.astype(np.int64) * n + rcv
    assert np.all(np.diff(key) > 0)          # sorted, so no duplicate pair
    back = np.sort(rcv.astype(np.int64) * n + snd)
    assert np.array_equal(back, key)         # every edge in both directions


def test_heavy_tailed_in_degrees():
    n = 50_000
    _, rcv = graphgen.simple_symmetric_graph(n, 400_000, 0.45, seed=1)
    deg = np.bincount(rcv, minlength=n)
    assert deg.max() > 20 * deg.mean()


def test_same_seed_same_pairs_other_seed_other_pairs():
    a = graphgen.simple_symmetric_graph(2708, 5278, 0.6, seed=1609)
    b = graphgen.simple_symmetric_graph(2708, 5278, 0.6, seed=1609)
    c = graphgen.simple_symmetric_graph(2708, 5278, 0.6, seed=1610)
    assert np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])
    assert not np.array_equal(a[1], c[1])


def test_row_pointer():
    snd, rcv = graphgen.simple_symmetric_graph(300, 900, 0.6, seed=2)
    ptr = graphgen.in_row_ptr(rcv, 300)
    assert ptr[0] == 0 and ptr[-1] == rcv.size
    assert np.array_equal(np.diff(ptr), np.bincount(rcv, minlength=300))


@pytest.mark.parametrize("cap", [1, 4, 7, 64, 500, 2708, 5000])
def test_fast_reference_counts_match_unique_counts(cap):
    snd, rcv = graphgen.simple_symmetric_graph(2708, 5278, 0.6, seed=5)
    fast = reference.tile_counts(snd, rcv, 2708, cap)
    slow = reference.tile_counts_by_unique(snd, rcv, 2708, cap)
    assert fast["n_tiles"] == slow["n_tiles"] and fast["K"] == slow["K"]
    for f in reference.COUNT_FIELDS:
        assert np.array_equal(fast[f], slow[f]), f
    assert fast["vertex_counts"].sum() == 2708
    assert fast["edge_counts"].sum() == snd.size
    assert np.all(fast["halo_counts"] <= fast["remote_edge_counts"])


def test_reference_forward_matches_dense_float64():
    import graphs

    n = 200
    snd, rcv = graphgen.simple_symmetric_graph(n, 600, 0.6, seed=4)
    rng = np.random.default_rng(0)
    x = rng.standard_normal((256, 12))
    w1 = rng.standard_normal((12, 5))
    w2 = rng.standard_normal((5, 3))
    a = graphs.dense_gcn_adjacency(snd, rcv, n, 256).astype(np.float64)
    dense = a @ np.maximum(a @ x @ w1, 0.0) @ w2
    got = reference.gcn_forward(x, w1, w2, snd, rcv, n)
    # Â itself is float32, so agreement is to its rounding.
    np.testing.assert_allclose(got, dense[:n], rtol=1e-6, atol=1e-6)
    assert np.all(dense[n:] == 0.0)
