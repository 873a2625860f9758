"""Mixture-of-Experts FFN: routing, capacity dispatch, expert parallelism.

Three execution paths, one semantics:

* :func:`moe_ffn_reference` — every expert processes every token, gated
  combine.  O(E) overcompute; used as the numerical oracle in tests and as
  the decode path (at decode batch sizes all experts are hit anyway, and the
  step is weight-read-bound — see DESIGN.md).
* :func:`moe_ffn_capacity` — single-device capacity-bucketed dispatch:
  tokens scatter into an (E, C, d) buffer, batched expert GEMMs, gather back.
  Active-only FLOPs (x capacity factor).  This is what the EP path reduces
  to on one device.
* :func:`moe_ffn_ep` — expert-parallel shard_map: tokens are
  sequence-sharded over the ``model`` axis, packed into per-destination
  capacity buckets, exchanged with ``all_to_all`` (dispatch), processed by
  the shard-local experts, and returned with a second ``all_to_all``
  (combine).  This is the production path whose two a2a ops per layer are
  the traffic characterized by
  :func:`repro.core.tpu_model.moe_dispatch_sync`.

Over-capacity assignments are dropped (standard Switch/GShard semantics);
the capacity factor is configurable per arch config.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

Array = jax.Array


@dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_ff_expert: int
    capacity_factor: float = 1.25
    # Snowflake-Arctic-style dense residual MLP running in parallel with the
    # experts (d_ff of that branch); None disables it.
    dense_residual_d_ff: Optional[int] = None
    aux_loss_weight: float = 0.01


def router_topk(x: Array, w_router: Array, cfg: MoEConfig):
    """Softmax-then-top-k routing with renormalized gates (Qwen3/Mixtral).

    Returns (expert_idx (T,k) int32, gates (T,k) f32, aux_loss scalar).
    """
    logits = x.astype(jnp.float32) @ w_router.astype(jnp.float32)  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, cfg.top_k)
    gates = gate_vals / (jnp.sum(gate_vals, axis=-1, keepdims=True) + 1e-9)
    # Switch-style load-balance auxiliary loss.
    me = jnp.mean(probs, axis=0)                                   # (E,)
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(expert_idx, cfg.n_experts, dtype=jnp.float32), axis=1),
        axis=0)
    aux = cfg.n_experts * jnp.sum(me * ce) * cfg.aux_loss_weight
    return expert_idx, gates, aux


def _expert_ffn(h: Array, w_gate: Array, w_up: Array, w_down: Array) -> Array:
    """SwiGLU expert: h (..., d); weights (..., d, f) / (..., f, d)."""
    a = jnp.einsum("...gd,...df->...gf", h, w_gate)
    b = jnp.einsum("...gd,...df->...gf", h, w_up)
    return jnp.einsum("...gf,...fd->...gd", jax.nn.silu(a) * b, w_down)


def moe_ffn_reference(params: dict, x: Array, cfg: MoEConfig):
    """All-expert compute with gated combine.  x: (T, d)."""
    expert_idx, gates, aux = router_topk(x, params["router"], cfg)
    # (E, T, d): every expert sees every token.
    h = _expert_ffn(x[None].astype(x.dtype),
                    params["w_gate"], params["w_up"], params["w_down"])
    mask = jax.nn.one_hot(expert_idx, cfg.n_experts, dtype=jnp.float32)  # (T,k,E)
    weights = jnp.einsum("tk,tke->et", gates, mask).astype(x.dtype)      # (E,T)
    out = jnp.einsum("et,etd->td", weights, h)
    return out, aux


def _capacity(tokens: int, cfg: MoEConfig) -> int:
    return max(1, math.ceil(tokens * cfg.top_k * cfg.capacity_factor
                            / cfg.n_experts))


def _pack_assignments(x: Array, expert_idx: Array, gates: Array,
                      n_experts: int, capacity: int):
    """Flatten (token, k) assignments and compute per-expert slot positions.

    Returns (token_of_assignment, flat_expert, slot, keep, flat_gate).
    """
    T, k = expert_idx.shape
    flat_e = expert_idx.reshape(-1)                                  # (A,)
    flat_g = gates.reshape(-1)
    token_of = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
    onehot = jax.nn.one_hot(flat_e, n_experts, dtype=jnp.int32)      # (A, E)
    pos = jnp.take_along_axis(jnp.cumsum(onehot, axis=0),
                              flat_e[:, None], axis=1)[:, 0] - 1     # (A,)
    keep = pos < capacity
    slot = jnp.where(keep, pos, 0)
    return token_of, flat_e, jax.lax.stop_gradient(slot), keep, flat_g


def moe_ffn_capacity(params: dict, x: Array, cfg: MoEConfig):
    """Single-device capacity-bucketed dispatch.  x: (T, d)."""
    T, d = x.shape
    C = _capacity(T, cfg)
    expert_idx, gates, aux = router_topk(x, params["router"], cfg)
    token_of, flat_e, slot, keep, flat_g = _pack_assignments(
        x, expert_idx, gates, cfg.n_experts, C)
    x_a = x[token_of] * keep[:, None].astype(x.dtype)
    buf = jnp.zeros((cfg.n_experts, C, d), x.dtype).at[flat_e, slot].add(x_a)
    out_buf = _expert_ffn(buf, params["w_gate"], params["w_up"], params["w_down"])
    y_a = out_buf[flat_e, slot] * (keep.astype(jnp.float32) * flat_g)[:, None].astype(x.dtype)
    out = jax.ops.segment_sum(y_a, token_of, num_segments=T)
    return out, aux


def moe_ffn_ep(params: dict, x: Array, cfg: MoEConfig, *, axis_name: str):
    """Expert-parallel dispatch inside shard_map.

    Called per shard: x (T_loc, d); expert weights are the shard-local slice
    (E_loc, d, f).  Two all_to_all ops move capacity buckets to/from expert
    owners.
    """
    ep = jax.lax.axis_size(axis_name)
    E, E_loc = cfg.n_experts, cfg.n_experts // ep
    T, d = x.shape
    C = _capacity(T, cfg)  # per-expert capacity contributed by this sender

    expert_idx, gates, aux = router_topk(x, params["router"], cfg)
    token_of, flat_e, slot, keep, flat_g = _pack_assignments(
        x, expert_idx, gates, E, C)
    dest = flat_e // E_loc
    local_e = flat_e % E_loc

    x_a = x[token_of] * keep[:, None].astype(x.dtype)
    send = jnp.zeros((ep, E_loc, C, d), x.dtype).at[dest, local_e, slot].add(x_a)
    recv = jax.lax.all_to_all(send, axis_name, split_axis=0, concat_axis=0)
    # (ep_src, E_loc, C, d) -> (E_loc, ep_src * C, d): batched local-expert GEMM.
    h = recv.transpose(1, 0, 2, 3).reshape(E_loc, ep * C, d)
    out = _expert_ffn(h, params["w_gate"], params["w_up"], params["w_down"])
    back = out.reshape(E_loc, ep, C, d).transpose(1, 0, 2, 3)
    ret = jax.lax.all_to_all(back, axis_name, split_axis=0, concat_axis=0)
    y_a = ret[dest, local_e, slot] * (keep.astype(jnp.float32) * flat_g)[:, None].astype(x.dtype)
    y = jax.ops.segment_sum(y_a, token_of, num_segments=T)
    # aux loss is computed on local routing stats; average across shards.
    aux = jax.lax.pmean(aux, axis_name)
    return y, aux


def init_moe_params(rng: Array, d_model: int, cfg: MoEConfig,
                    *, dtype=jnp.float32) -> dict:
    from .common import dense_init
    k1, k2, k3, k4 = jax.random.split(rng, 4)
    f = cfg.d_ff_expert
    return {
        "router": dense_init(k1, (d_model, cfg.n_experts), dtype=dtype),
        "w_gate": dense_init(k2, (cfg.n_experts, d_model, f), fan_in=d_model, dtype=dtype),
        "w_up": dense_init(k3, (cfg.n_experts, d_model, f), fan_in=d_model, dtype=dtype),
        "w_down": dense_init(k4, (cfg.n_experts, f, d_model), fan_in=f, dtype=dtype),
    }
