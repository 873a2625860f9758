"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def fused_aggregate_combine_ref(adjacency: jax.Array, x: jax.Array,
                                w: jax.Array) -> jax.Array:
    """Y = (A @ X) @ W in fp32 at full precision.

    ``Precision.HIGHEST``: a TPU's default f32 dot is a reduced-precision
    MXU pass, which would make the oracle, not the kernel, miss the
    numerics bar."""
    hi = jax.lax.Precision.HIGHEST
    agg = jnp.dot(adjacency.astype(jnp.float32), x.astype(jnp.float32),
                  precision=hi)
    return jnp.dot(agg, w.astype(jnp.float32), precision=hi).astype(x.dtype)


def edge_list_aggregate_ref(x: jax.Array, senders: jax.Array,
                            receivers: jax.Array, weights: jax.Array,
                            n_nodes: int) -> jax.Array:
    """Edge-list semantics the block-dense adjacency must reproduce."""
    msgs = x[senders] * weights[:, None]
    return jax.ops.segment_sum(msgs, receivers, num_segments=n_nodes)


def flash_attention_ref(q: jax.Array, k: jax.Array, v: jax.Array,
                        *, causal: bool = True,
                        window: int | None = None) -> jax.Array:
    """(B, S, H, D) attention oracle in fp32."""
    b, s, h, d = q.shape
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * d ** -0.5
    qpos = jnp.arange(s)[:, None]
    kpos = jnp.arange(s)[None, :]
    mask = jnp.ones((s, s), bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= (qpos - kpos) < window
    scores = jnp.where(mask[None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)


def embedding_bag_ref(table: jax.Array, indices: jax.Array) -> jax.Array:
    """(V, D) table, (B, hot) indices -> (B, D) summed bags."""
    return jnp.take(table, indices, axis=0).sum(axis=1)
