"""Per-kernel allclose sweeps against the pure-jnp oracles (interpret mode
on the CPU),
plus hypothesis property tests on the kernels' invariants."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref

RNG = np.random.default_rng(0)


def _rel(a, b):
    return float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-9))


# ---------------------------------------------------------------------------
# Fused aggregate+combine (the paper's aggregation hot spot)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,f,t,bn,bk", [
    (256, 32, 8, 128, 128),
    (512, 64, 16, 128, 256),
    (512, 128, 32, 256, 256),
    (1024, 16, 7, 256, 512),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_aggregate_combine(n, f, t, bn, bk, dtype):
    a = (RNG.random((n, n)) < 0.02).astype(np.float32) * RNG.random((n, n))
    x = RNG.standard_normal((n, f))
    w = RNG.standard_normal((f, t))
    a, x, w = (jnp.asarray(v, dtype) for v in (a, x, w))
    out = ops.gnn_aggregate_combine(a, x, w, block_n=bn, block_k=bk)
    expect = ref.fused_aggregate_combine_ref(a, x, w)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    assert _rel(out.astype(jnp.float32), expect.astype(jnp.float32)) < tol


def test_fused_kernel_matches_edge_list_semantics():
    """Block-dense adjacency path == edge-list segment_sum path."""
    n, f, t, e = 256, 24, 8, 900
    snd = RNG.integers(0, n, e)
    rcv = RNG.integers(0, n, e)
    wgt = RNG.random(e).astype(np.float32)
    a = np.zeros((n, n), np.float32)
    np.add.at(a, (rcv, snd), wgt)
    x = jnp.asarray(RNG.standard_normal((n, f)), jnp.float32)
    w = jnp.asarray(RNG.standard_normal((f, t)), jnp.float32)
    agg = ref.edge_list_aggregate_ref(x, jnp.asarray(snd), jnp.asarray(rcv),
                                      jnp.asarray(wgt), n)
    expect = (agg @ w)
    out = ops.gnn_aggregate_combine(jnp.asarray(a), x, w, block_n=128, block_k=128)
    assert _rel(out, expect) < 1e-4


@settings(max_examples=15, deadline=None)
@given(st.integers(1, 4), st.integers(1, 4), st.integers(1, 3))
def test_fused_kernel_linearity(nb, kb, seed):
    """Property: kernel is linear in X — f(X1+X2) == f(X1)+f(X2)."""
    rng = np.random.default_rng(seed)
    n, f, t = 128 * nb, 16, 8
    bk = 128 * kb
    if n % bk:
        bk = n
    a = jnp.asarray((rng.random((n, n)) < 0.05).astype(np.float32))
    x1 = jnp.asarray(rng.standard_normal((n, f)), jnp.float32)
    x2 = jnp.asarray(rng.standard_normal((n, f)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((f, t)), jnp.float32)
    f12 = ops.gnn_aggregate_combine(a, x1 + x2, w, block_n=128, block_k=bk)
    f1 = ops.gnn_aggregate_combine(a, x1, w, block_n=128, block_k=bk)
    f2 = ops.gnn_aggregate_combine(a, x2, w, block_n=128, block_k=bk)
    assert _rel(f12, f1 + f2) < 1e-4


# ---------------------------------------------------------------------------
# Flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("s,d,bq,bk,window", [
    (128, 64, 64, 64, None),
    (256, 64, 128, 64, None),
    (256, 32, 64, 128, 64),
    (512, 128, 128, 128, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention(s, d, bq, bk, window, dtype):
    b, h = 2, 2
    q = jnp.asarray(RNG.standard_normal((b, s, h, d)), dtype)
    k = jnp.asarray(RNG.standard_normal((b, s, h, d)), dtype)
    v = jnp.asarray(RNG.standard_normal((b, s, h, d)), dtype)
    out = ops.flash_attention(q, k, v, window=window, block_q=bq, block_k=bk)
    expect = ref.flash_attention_ref(q, k, v, causal=True, window=window)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    assert _rel(out.astype(jnp.float32), expect.astype(jnp.float32)) < tol


def test_flash_attention_gqa():
    b, s, h, hk, d = 2, 128, 8, 2, 32
    q = jnp.asarray(RNG.standard_normal((b, s, h, d)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((b, s, hk, d)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((b, s, hk, d)), jnp.float32)
    out = ops.flash_attention(q, k, v, block_q=64, block_k=64)
    kf = jnp.repeat(k, h // hk, axis=2)
    vf = jnp.repeat(v, h // hk, axis=2)
    expect = ref.flash_attention_ref(q, kf, vf, causal=True)
    assert _rel(out, expect) < 2e-5


def test_flash_attention_softcap():
    b, s, h, d = 1, 128, 2, 32
    q = jnp.asarray(RNG.standard_normal((b, s, h, d)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((b, s, h, d)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((b, s, h, d)), jnp.float32)
    out = ops.flash_attention(q, k, v, softcap=8.0, block_q=64, block_k=64)
    # oracle with softcap
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * d ** -0.5
    scores = 8.0 * jnp.tanh(scores / 8.0)
    mask = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(mask[None, None], scores, -1e30)
    expect = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
    assert _rel(out, expect) < 2e-5


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 2 ** 31 - 1))
def test_flash_attention_rows_are_convex_combos(seed):
    """Property: each output row lies in the convex hull of V rows, so its
    max is bounded by V's max (softmax weights sum to 1)."""
    rng = np.random.default_rng(seed)
    b, s, h, d = 1, 128, 1, 16
    q = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    out = ops.flash_attention(q, k, v, block_q=64, block_k=64)
    assert float(jnp.max(out)) <= float(jnp.max(v)) + 1e-4
    assert float(jnp.min(out)) >= float(jnp.min(v)) - 1e-4


# ---------------------------------------------------------------------------
# Embedding bag
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("v,d,b,hot", [
    (128, 64, 8, 1),
    (1000, 128, 32, 4),
    (4096, 256, 16, 8),
])
def test_embedding_bag(v, d, b, hot):
    tab = jnp.asarray(RNG.standard_normal((v, d)), jnp.float32)
    idx = jnp.asarray(RNG.integers(0, v, (b, hot)), jnp.int32)
    out = ops.embedding_bag(tab, idx)
    expect = ref.embedding_bag_ref(tab, idx)
    assert _rel(out, expect) < 1e-6


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 2 ** 31 - 1))
def test_embedding_bag_permutation_invariant(seed):
    """Property: sum-pooling is invariant to bag order."""
    rng = np.random.default_rng(seed)
    v, d, b, hot = 64, 32, 4, 6
    tab = jnp.asarray(rng.standard_normal((v, d)), jnp.float32)
    idx = rng.integers(0, v, (b, hot))
    perm = rng.permutation(hot)
    o1 = ops.embedding_bag(tab, jnp.asarray(idx, jnp.int32))
    o2 = ops.embedding_bag(tab, jnp.asarray(idx[:, perm], jnp.int32))
    assert _rel(o1, o2) < 1e-5
