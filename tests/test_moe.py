"""MoE dispatch tests: routing semantics, capacity drops, path equivalence."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from hypothesis import given, settings, strategies as st

from repro.models.moe import (MoEConfig, init_moe_params, moe_ffn_capacity,
                              moe_ffn_reference, router_topk)


def _setup(t=32, d=16, e=4, k=2, cf=8.0, seed=0):
    cfg = MoEConfig(n_experts=e, top_k=k, d_ff_expert=24, capacity_factor=cf)
    params = init_moe_params(jax.random.key(seed), d, cfg)
    x = jax.random.normal(jax.random.key(seed + 1), (t, d))
    return cfg, params, x


def test_capacity_matches_reference_when_no_drops():
    cfg, params, x = _setup(cf=16.0)
    ref, _ = moe_ffn_reference(params, x, cfg)
    cap, _ = moe_ffn_capacity(params, x, cfg)
    err = float(jnp.max(jnp.abs(ref - cap)) / (jnp.max(jnp.abs(ref)) + 1e-9))
    assert err < 1e-5, err


def test_gates_normalized_and_topk_unique():
    cfg, params, x = _setup()
    idx, gates, aux = router_topk(x, params["router"], cfg)
    assert np.allclose(np.asarray(gates.sum(-1)), 1.0, atol=1e-5)
    for row in np.asarray(idx):
        assert len(set(row.tolist())) == cfg.top_k
    assert float(aux) >= 0


def test_capacity_drops_reduce_output_norm():
    """With a tiny capacity factor some assignments drop; the capacity path
    must produce a smaller-or-equal contribution than the reference."""
    cfg, params, x = _setup(t=64, cf=16.0)
    tight = MoEConfig(n_experts=cfg.n_experts, top_k=cfg.top_k,
                      d_ff_expert=cfg.d_ff_expert, capacity_factor=0.25)
    full, _ = moe_ffn_capacity(params, x, cfg)
    dropped, _ = moe_ffn_capacity(params, x, tight)
    assert float(jnp.linalg.norm(dropped)) < float(jnp.linalg.norm(full))


def test_grads_flow_through_dispatch():
    cfg, params, x = _setup()
    g = jax.grad(lambda p: jnp.sum(moe_ffn_capacity(p, x, cfg)[0] ** 2))(params)
    for leaf in jax.tree_util.tree_leaves(g):
        assert jnp.isfinite(leaf).all()
    assert float(jnp.max(jnp.abs(g["w_gate"]))) > 0


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 2 ** 31 - 1), st.sampled_from([1, 2, 4]))
def test_capacity_path_token_permutation_equivariance(seed, k):
    """Property: permuting tokens permutes outputs (no cross-token state)."""
    cfg, params, x = _setup(t=16, k=k, cf=16.0, seed=seed % 1000)
    perm = np.random.default_rng(seed).permutation(16)
    y1, _ = moe_ffn_capacity(params, x, cfg)
    y2, _ = moe_ffn_capacity(params, x[perm], cfg)
    err = float(jnp.max(jnp.abs(y1[perm] - y2)) / (jnp.max(jnp.abs(y1)) + 1e-9))
    assert err < 1e-4, err
