"""Blocked online-softmax (flash) attention Pallas kernel.

Replaces the HLO-level chunked attention of
:mod:`repro.models.attention` on real TPU hardware: the (BQ, BK) score tile
never leaves VMEM, with running max / sum-exp accumulators carried across
KV blocks — the transformer-side analogue of keeping the paper's
inter-phase traffic on-chip.

Layout: inputs are flattened to (B*H, S, D) by ops.py; the grid is
(batch*heads, q blocks, kv blocks) with the kv dimension innermost so the
accumulators live across the inner loop.  Supports causal and
sliding-window masking (gemma2's local layers).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import resolve_interpret

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
_NEG = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            block_q: int, block_k: int, n_kv_blocks: int, scale: float,
            causal: bool, window: Optional[int], softcap: Optional[float]):
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32) * scale          # (BQ, D)
    k = k_ref[0].astype(jnp.float32)                  # (BK, D)
    v = v_ref[0].astype(jnp.float32)                  # (BK, D)
    scores = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
    if softcap is not None:
        scores = softcap * jnp.tanh(scores / softcap)

    qpos = i * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    kpos = j * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    mask = jnp.ones((block_q, block_k), jnp.bool_)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= (qpos - kpos) < window
    scores = jnp.where(mask, scores, _NEG)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(scores, axis=1))
    p = jnp.where(mask, jnp.exp(scores - m_new[:, None]), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1)
    acc_ref[...] = acc_ref[...] * corr[:, None] + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(j == n_kv_blocks - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, ...] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention_bhsd(q: jax.Array, k: jax.Array, v: jax.Array, *,
                         causal: bool = True, window: Optional[int] = None,
                         softcap: Optional[float] = None,
                         block_q: int = DEFAULT_BLOCK_Q,
                         block_k: int = DEFAULT_BLOCK_K,
                         interpret: Optional[bool] = None) -> jax.Array:
    """q, k, v: (BH, S, D) -> (BH, S, D)."""
    bh, s, d = q.shape
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    assert s % block_q == 0 and s % block_k == 0, (s, block_q, block_k)
    grid = (bh, s // block_q, s // block_k)
    scale = d ** -0.5

    return pl.pallas_call(
        functools.partial(_kernel, block_q=block_q, block_k=block_k,
                          n_kv_blocks=grid[2], scale=scale, causal=causal,
                          window=window, softcap=softcap),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(q, k, v)
