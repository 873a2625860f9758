"""Decoder-only transformer covering the five assigned LM architectures.

One config class expresses dense (granite, smollm), alternating local/global
with soft-caps (gemma2), and MoE with optional dense residual branch
(qwen3-moe, arctic).  Layers run as a ``lax.scan`` over *pattern groups* —
gemma2's (local, global) alternation becomes a 2-entry pattern whose KV
caches are sized per entry (the local entries keep a ring buffer of
``window`` slots, the global entries the full sequence) — so the compiled
HLO stays one-layer-sized and 500k-token decode does not over-allocate.

Distribution (via :class:`repro.distributed.ShardingPolicy`):
* batch over the dp axes; residual stream sequence-sharded over ``model``
  (Megatron-SP) when the policy enables it;
* attention TP over heads when ``n_heads % tp == 0``, otherwise context
  parallelism (shard_map over ``model``: q stays sequence-sharded, kv is
  all-gathered — the layout used by gemma2's 8-head / smollm's 9-head
  configs on a 16-wide model axis);
* MoE experts sharded over ``model`` (EP) with capacity-bucketed all-to-all
  dispatch (:func:`repro.models.moe.moe_ffn_ep`);
* decode KV caches sequence-sharded over configurable axes with
  flash-decoding partial-softmax combination.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field, replace
from functools import partial
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from . import attention as attn_lib
from . import moe as moe_lib
from .common import (apply_rope, cross_entropy_loss, dense_init, embed_init,
                     rms_norm, rope_freqs, softcap)
from .moe import MoEConfig
from ..distributed.sharding import ShardingPolicy

Array = jax.Array


@dataclass(frozen=True)
class TransformerConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_head: int
    d_ff: int
    vocab: int
    moe: Optional[MoEConfig] = None
    # Repeating per-layer window pattern; None entries are global-causal.
    # gemma2: (4096, None).  Length must divide n_layers.
    window_pattern: tuple[Optional[int], ...] = (None,)
    attn_softcap: Optional[float] = None
    final_softcap: Optional[float] = None
    rope_theta: float = 10000.0
    dtype: str = "bfloat16"
    remat: str = "full"              # "none" | "full" | "dots"
    q_chunk: int = 1024
    tie_embeddings: bool = True

    def __post_init__(self):
        assert self.n_layers % len(self.window_pattern) == 0, (
            self.name, self.n_layers, self.window_pattern)
        assert self.n_heads % self.n_kv_heads == 0

    @property
    def n_groups(self) -> int:
        return self.n_layers // len(self.window_pattern)

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)

    def param_count(self) -> int:
        d, H, Hk, dh = self.d_model, self.n_heads, self.n_kv_heads, self.d_head
        attn = d * (H * dh) + 2 * d * (Hk * dh) + (H * dh) * d
        per_layer = attn + 2 * d  # + norms
        if self.moe is not None:
            m = self.moe
            per_layer += d * m.n_experts + 3 * m.n_experts * d * m.d_ff_expert
            if m.dense_residual_d_ff:
                per_layer += 3 * d * m.dense_residual_d_ff
        else:
            per_layer += 3 * d * self.d_ff
        total = self.n_layers * per_layer + self.vocab * d + d
        if not self.tie_embeddings:
            total += self.vocab * d
        return total

    def active_param_count(self) -> int:
        """Parameters touched per token (MoE: top_k of n_experts)."""
        if self.moe is None:
            return self.param_count()
        m = self.moe
        d = self.d_model
        expert_all = 3 * m.n_experts * d * m.d_ff_expert
        expert_act = 3 * m.top_k * d * m.d_ff_expert
        return self.param_count() - self.n_layers * (expert_all - expert_act)


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

def init_params(cfg: TransformerConfig, rng: Array, *, dtype=jnp.float32) -> dict:
    d, H, Hk, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    G = cfg.n_groups
    keys = jax.random.split(rng, 2 + len(cfg.window_pattern))

    def block_params(key: Array) -> dict:
        ks = jax.random.split(key, 8)
        blk = {
            "ln1": jnp.zeros((G, d), dtype),
            "ln2": jnp.zeros((G, d), dtype),
            "wq": dense_init(ks[0], (G, d, H * dh), fan_in=d, dtype=dtype),
            "wk": dense_init(ks[1], (G, d, Hk * dh), fan_in=d, dtype=dtype),
            "wv": dense_init(ks[2], (G, d, Hk * dh), fan_in=d, dtype=dtype),
            "wo": dense_init(ks[3], (G, H * dh, d), fan_in=H * dh, dtype=dtype),
        }
        if cfg.moe is not None:
            m = cfg.moe
            moe_keys = jax.random.split(ks[4], G)
            stacked = jax.vmap(lambda k: moe_lib.init_moe_params(
                k, d, m, dtype=dtype))(moe_keys)
            blk["moe"] = stacked
            if m.dense_residual_d_ff:
                f = m.dense_residual_d_ff
                blk["res_gate"] = dense_init(ks[5], (G, d, f), fan_in=d, dtype=dtype)
                blk["res_up"] = dense_init(ks[6], (G, d, f), fan_in=d, dtype=dtype)
                blk["res_down"] = dense_init(ks[7], (G, f, d), fan_in=f, dtype=dtype)
        else:
            blk["w_gate"] = dense_init(ks[5], (G, d, cfg.d_ff), fan_in=d, dtype=dtype)
            blk["w_up"] = dense_init(ks[6], (G, d, cfg.d_ff), fan_in=d, dtype=dtype)
            blk["w_down"] = dense_init(ks[7], (G, cfg.d_ff, d), fan_in=cfg.d_ff, dtype=dtype)
        return blk

    params = {
        "embed": embed_init(keys[0], (cfg.vocab, d), dtype=dtype),
        "final_norm": jnp.zeros((d,), dtype),
        "blocks": [block_params(k) for k in keys[2:]],
    }
    if not cfg.tie_embeddings:
        params["unembed"] = dense_init(keys[1], (d, cfg.vocab), fan_in=d, dtype=dtype)
    return params


def abstract_params(cfg: TransformerConfig, *, dtype=jnp.float32):
    """Parameter tree as ShapeDtypeStructs — no allocation (dry-run path)."""
    return jax.eval_shape(
        lambda k: init_params(cfg, k, dtype=dtype), jax.random.key(0))


def param_pspecs(cfg: TransformerConfig, policy: ShardingPolicy) -> dict:
    """PartitionSpec tree matching init_params' structure."""
    tp = policy.tp_axis
    tp_heads = cfg.n_heads % policy.tp == 0 and cfg.n_kv_heads % policy.tp == 0

    def block_spec() -> dict:
        hspec = tp if tp_heads else None
        blk = {
            "ln1": P(None, None), "ln2": P(None, None),
            "wq": P(None, None, hspec),
            "wk": P(None, None, hspec),
            "wv": P(None, None, hspec),
            "wo": P(None, hspec, None),
        }
        if cfg.moe is not None:
            blk["moe"] = {
                "router": P(None, None, None),
                "w_gate": P(None, tp, None, None),
                "w_up": P(None, tp, None, None),
                "w_down": P(None, tp, None, None),
            }
            if cfg.moe.dense_residual_d_ff:
                blk["res_gate"] = P(None, None, tp)
                blk["res_up"] = P(None, None, tp)
                blk["res_down"] = P(None, tp, None)
        else:
            blk["w_gate"] = P(None, None, tp)
            blk["w_up"] = P(None, None, tp)
            blk["w_down"] = P(None, tp, None)
        return blk

    specs = {
        "embed": P(tp, None) if cfg.vocab % policy.tp == 0 else P(None, None),
        "final_norm": P(None),
        "blocks": [block_spec() for _ in cfg.window_pattern],
    }
    if not cfg.tie_embeddings:
        specs["unembed"] = P(None, tp) if cfg.vocab % policy.tp == 0 else P(None, None)
    return specs


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------

def _attention_block(cfg: TransformerConfig, blk: dict, x: Array,
                     window: Optional[int], policy: Optional[ShardingPolicy],
                     freqs: Array) -> Array:
    b, s, d = x.shape
    H, Hk, dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    h = rms_norm(x, blk["ln1"])
    tp_heads = (policy is None or
                (H % policy.tp == 0 and Hk % policy.tp == 0))

    q = (h @ blk["wq"]).reshape(b, s, H, dh)
    k = (h @ blk["wk"]).reshape(b, s, Hk, dh)
    v = (h @ blk["wv"]).reshape(b, s, Hk, dh)
    positions = jnp.arange(s, dtype=jnp.int32)[None]
    q = apply_rope(q, positions, freqs)
    k = apply_rope(k, positions, freqs)

    if policy is not None and tp_heads:
        dp, tp = policy.dp_spec, policy.tp_axis
        q = policy.constrain(q, P(dp, None, tp, None))
        k = policy.constrain(k, P(dp, None, tp, None))
        v = policy.constrain(v, P(dp, None, tp, None))
        out = attn_lib.chunked_causal_attention(
            q, k, v, window=window, attn_softcap=cfg.attn_softcap,
            q_chunk=cfg.q_chunk, shard_divisor=policy.n_devices)
    elif policy is not None:
        out = _context_parallel_attention(cfg, policy, q, k, v, window)
    else:
        out = attn_lib.chunked_causal_attention(
            q, k, v, window=window, attn_softcap=cfg.attn_softcap,
            q_chunk=cfg.q_chunk)

    out = out.reshape(b, s, H * dh) @ blk["wo"]
    if policy is not None:
        out = policy.constrain(out, policy.act_spec())
    return x + out


def _context_parallel_attention(cfg, policy, q, k, v, window):
    """shard_map context parallelism: q sequence-sharded, kv all-gathered.

    Used when the head count does not divide the model axis (gemma2: 8 heads,
    smollm: 9 heads on tp=16).
    """
    tp_axis = policy.tp_axis
    dp = policy.dp_spec
    mesh = policy.mesh
    s = q.shape[1]
    s_loc = s // policy.tp

    def local(qs, ks, vs):
        r = jax.lax.axis_index(tp_axis)
        kg = jax.lax.all_gather(ks, tp_axis, axis=1, tiled=True)
        vg = jax.lax.all_gather(vs, tp_axis, axis=1, tiled=True)
        return attn_lib.chunked_causal_attention(
            qs, kg, vg, window=window, attn_softcap=cfg.attn_softcap,
            q_chunk=min(cfg.q_chunk, s_loc), q_offset=r * s_loc)

    spec_q = P(dp, tp_axis, None, None)
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(spec_q, spec_q, spec_q),
        out_specs=spec_q,
        check_vma=False,
    )(q, k, v)


def _ffn_block(cfg: TransformerConfig, blk: dict, x: Array,
               policy: Optional[ShardingPolicy]) -> tuple[Array, Array]:
    b, s, d = x.shape
    h = rms_norm(x, blk["ln2"])
    aux = jnp.zeros((), jnp.float32)
    if cfg.moe is None:
        gate = h @ blk["w_gate"]
        up = h @ blk["w_up"]
        out = (jax.nn.silu(gate) * up) @ blk["w_down"]
    else:
        flat = h.reshape(b * s, d)
        if policy is not None and cfg.moe.n_experts % policy.tp == 0:
            out, aux = _moe_ep_sharded(cfg, policy, blk["moe"], h)
        else:
            out2, aux = moe_lib.moe_ffn_capacity(blk["moe"], flat, cfg.moe)
            out = out2.reshape(b, s, d)
        if cfg.moe.dense_residual_d_ff:
            res = (jax.nn.silu(h @ blk["res_gate"]) * (h @ blk["res_up"])) @ blk["res_down"]
            out = out + res
    if policy is not None:
        out = policy.constrain(out, policy.act_spec())
    return x + out, aux


def _moe_ep_sharded(cfg, policy, moe_params, h):
    """Sequence-shard tokens over the model axis, run EP all-to-all MoE."""
    tp_axis = policy.tp_axis
    dp = policy.dp_spec
    b, s, d = h.shape

    def local(params_loc, h_loc):
        bl, sl, _ = h_loc.shape
        flat = h_loc.reshape(bl * sl, d)
        y, aux = moe_lib.moe_ffn_ep(params_loc, flat, cfg.moe, axis_name=tp_axis)
        # Replicate the aux scalar across every mesh axis so the P() out-spec
        # is sound (routing stats differ per data shard otherwise).
        aux = jax.lax.pmean(aux, policy.mesh.axis_names)
        return y.reshape(bl, sl, d), aux

    pspecs = {
        "router": P(None, None),
        "w_gate": P(tp_axis, None, None),
        "w_up": P(tp_axis, None, None),
        "w_down": P(tp_axis, None, None),
    }
    out, aux = jax.shard_map(
        local, mesh=policy.mesh,
        in_specs=(pspecs, P(dp, tp_axis, None)),
        out_specs=(P(dp, tp_axis, None), P()),
        check_vma=False,
    )(moe_params, h)
    return out, aux


def _decoder_group(cfg: TransformerConfig, policy: Optional[ShardingPolicy],
                   freqs: Array, x: Array, group_slices: Sequence[dict]):
    """Apply one pattern group: each entry with its own window config."""
    aux_total = jnp.zeros((), jnp.float32)
    for blk, window in zip(group_slices, cfg.window_pattern):
        x = _attention_block(cfg, blk, x, window, policy, freqs)
        x, aux = _ffn_block(cfg, blk, x, policy)
        aux_total = aux_total + aux
    return x, aux_total


# ---------------------------------------------------------------------------
# Forward / loss / train step
# ---------------------------------------------------------------------------

def forward_hidden(cfg: TransformerConfig, params: dict, tokens: Array,
                   *, policy: Optional[ShardingPolicy] = None) -> tuple[Array, Array]:
    """tokens (B, S) -> (final normed hidden (B, S, d), aux_loss)."""
    cdt = cfg.compute_dtype
    embed = params["embed"].astype(cdt)
    x = embed[tokens]
    if policy is not None:
        x = policy.constrain(x, policy.act_spec())
    freqs = rope_freqs(cfg.d_head, theta=cfg.rope_theta)

    blocks = [jax.tree_util.tree_map(lambda a: a.astype(cdt) if a.dtype in
                                     (jnp.float32, jnp.bfloat16) else a, b)
              for b in params["blocks"]]

    def body(carry, slices):
        x, aux = carry
        fn = partial(_decoder_group, cfg, policy, freqs)
        if cfg.remat == "full":
            fn = jax.checkpoint(fn, policy=jax.checkpoint_policies.nothing_saveable)
        elif cfg.remat == "dots":
            fn = jax.checkpoint(
                fn, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)
        x, a = fn(x, slices)
        return (x, aux + a), None

    (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                               xs=tuple(blocks))
    x = rms_norm(x, params["final_norm"].astype(cdt))
    return x, aux


def _unembed_weight(cfg: TransformerConfig, params: dict) -> Array:
    w = params["embed"].T if cfg.tie_embeddings else params["unembed"]
    return w.astype(cfg.compute_dtype)


def forward(cfg: TransformerConfig, params: dict, tokens: Array,
            *, policy: Optional[ShardingPolicy] = None) -> tuple[Array, Array]:
    """tokens (B, S) int32 -> (logits (B, S, V), aux_loss)."""
    x, aux = forward_hidden(cfg, params, tokens, policy=policy)
    unembed = _unembed_weight(cfg, params)
    if policy is not None and cfg.vocab % policy.tp == 0:
        # vocab-parallel logits: gather the sequence, shard the vocab.
        x = policy.constrain(x, P(policy.dp_spec, None, None))
    logits = x @ unembed
    if policy is not None and cfg.vocab % policy.tp == 0:
        logits = policy.constrain(logits, P(policy.dp_spec, None, policy.tp_axis))
    if cfg.final_softcap is not None:
        logits = softcap(logits, cfg.final_softcap)
    return logits, aux


# (B*S*V) elements above which the loss switches to sequence-chunked CE —
# the (B, S, V) logits tensor (and its cotangent) would otherwise dominate
# HBM at 32k+ vocab (the gemma2 dry-run found 19 GB of loss temps).
_CE_CHUNK_THRESHOLD = 1 << 24
_CE_CHUNK = 256


def _ce_token_nll(cfg, x_chunk, unembed, labels_chunk, policy):
    """(B, c, d) -> summed nll + count over one sequence chunk, fp32."""
    logits = x_chunk @ unembed
    if policy is not None and cfg.vocab % policy.tp == 0:
        logits = policy.constrain(logits, P(policy.dp_spec, None, policy.tp_axis))
    if cfg.final_softcap is not None:
        logits = softcap(logits, cfg.final_softcap)
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels_chunk[..., None], axis=-1)[..., 0]
    return jnp.sum(logz - gold)


def loss_fn(cfg: TransformerConfig, params: dict, batch: dict,
            *, policy: Optional[ShardingPolicy] = None) -> tuple[Array, dict]:
    tokens, labels = batch["tokens"], batch["labels"]
    b, s = tokens.shape
    x, aux = forward_hidden(cfg, params, tokens, policy=policy)
    unembed = _unembed_weight(cfg, params)

    if b * s * cfg.vocab <= _CE_CHUNK_THRESHOLD or s % _CE_CHUNK:
        if policy is not None and cfg.vocab % policy.tp == 0:
            x = policy.constrain(x, P(policy.dp_spec, None, None))
        logits = x @ unembed
        if cfg.final_softcap is not None:
            logits = softcap(logits, cfg.final_softcap)
        ce = cross_entropy_loss(logits, labels, mask=batch.get("mask"))
    else:
        # Sequence-chunked CE: logits for one chunk at a time, rematerialized
        # in the backward pass.
        n_chunks = s // _CE_CHUNK
        if policy is not None:
            x = policy.constrain(x, P(policy.dp_spec, None, None))
        xs = x.reshape(b, n_chunks, _CE_CHUNK, cfg.d_model).transpose(1, 0, 2, 3)
        ls = labels.reshape(b, n_chunks, _CE_CHUNK).transpose(1, 0, 2)
        chunk_fn = jax.checkpoint(
            lambda xc, lc: _ce_token_nll(cfg, xc, unembed, lc, policy),
            policy=jax.checkpoint_policies.nothing_saveable)

        def body(tot, xl):
            xc, lc = xl
            return tot + chunk_fn(xc, lc), None

        total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (xs, ls))
        ce = total / (b * s)

    loss = ce + aux
    return loss, {"loss": loss, "ce": ce, "aux": aux}


def make_train_step(cfg: TransformerConfig, optimizer,
                    *, policy: Optional[ShardingPolicy] = None):
    """Returns train_step(params, opt_state, batch) -> (params, opt_state, metrics)."""

    def train_step(params, opt_state, batch):
        grad_fn = jax.value_and_grad(
            lambda p: loss_fn(cfg, p, batch, policy=policy), has_aux=True)
        (loss, metrics), grads = grad_fn(params)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        from ..optim.optimizers import apply_updates
        params = apply_updates(params, updates)
        return params, opt_state, metrics

    return train_step


def make_prefill_step(cfg: TransformerConfig, *,
                      policy: Optional[ShardingPolicy] = None,
                      max_seq: Optional[int] = None):
    """Returns prefill(params, tokens (B,S)) -> (last_logits (B,V), cache).

    One inference prefill: the forward pass plus materialization of the KV
    cache (ring-local entries store the last ``window`` positions in ring
    layout, so decode can continue at pos = S).  ``max_seq`` sizes the cache
    for continued decoding (defaults to the prompt length).
    """

    def prefill(params, tokens):
        cdt = cfg.compute_dtype
        b, s = tokens.shape
        H, Hk, dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
        x = params["embed"].astype(cdt)[tokens]
        if policy is not None:
            x = policy.constrain(x, policy.act_spec())
        freqs = rope_freqs(cfg.d_head, theta=cfg.rope_theta)
        blocks = [jax.tree_util.tree_map(lambda a: a.astype(cdt), blk)
                  for blk in params["blocks"]]

        def group_body(x, slices):
            kvs = []
            for blk, window in zip(slices, cfg.window_pattern):
                h = rms_norm(x, blk["ln1"])
                q = (h @ blk["wq"]).reshape(b, s, H, dh)
                k = (h @ blk["wk"]).reshape(b, s, Hk, dh)
                v = (h @ blk["wv"]).reshape(b, s, Hk, dh)
                positions = jnp.arange(s, dtype=jnp.int32)[None]
                q = apply_rope(q, positions, freqs)
                k = apply_rope(k, positions, freqs)
                if policy is not None and (H % policy.tp == 0
                                           and Hk % policy.tp == 0):
                    dp, tp = policy.dp_spec, policy.tp_axis
                    q = policy.constrain(q, P(dp, None, tp, None))
                    k = policy.constrain(k, P(dp, None, tp, None))
                    v = policy.constrain(v, P(dp, None, tp, None))
                    out = attn_lib.chunked_causal_attention(
                        q, k, v, window=window, attn_softcap=cfg.attn_softcap,
                        q_chunk=cfg.q_chunk)
                elif policy is not None:
                    out = _context_parallel_attention(cfg, policy, q, k, v, window)
                else:
                    out = attn_lib.chunked_causal_attention(
                        q, k, v, window=window, attn_softcap=cfg.attn_softcap,
                        q_chunk=cfg.q_chunk)
                x = x + out.reshape(b, s, H * dh) @ blk["wo"]
                if policy is not None:
                    x = policy.constrain(x, policy.act_spec())
                x, _ = _ffn_block(cfg, blk, x, policy)
                # Cache entry: full sequence, or the last `window` slots in
                # ring layout so decode continues seamlessly at pos = s.
                target = max_seq or s
                s_entry = min(window, target) if window is not None else target
                if window is not None and s > s_entry:
                    kc = jnp.roll(k[:, s - s_entry:],
                                  shift=(s - s_entry) % s_entry, axis=1)
                    vc = jnp.roll(v[:, s - s_entry:],
                                  shift=(s - s_entry) % s_entry, axis=1)
                else:
                    pad = [(0, 0), (0, s_entry - s), (0, 0), (0, 0)]
                    kc = jnp.pad(k, pad) if s_entry > s else k
                    vc = jnp.pad(v, pad) if s_entry > s else v
                kvs.extend([kc, vc])
            return x, tuple(kvs)

        x, kv_stacks = jax.lax.scan(group_body, x, xs=tuple(blocks))
        x = rms_norm(x[:, -1:], params["final_norm"].astype(cdt))
        unembed = (params["embed"].T if cfg.tie_embeddings
                   else params["unembed"]).astype(cdt)
        logits = (x @ unembed)[:, 0]
        if cfg.final_softcap is not None:
            logits = softcap(logits, cfg.final_softcap)
        cache = {}
        for i in range(len(cfg.window_pattern)):
            cache[f"k{i}"] = kv_stacks[2 * i]
            cache[f"v{i}"] = kv_stacks[2 * i + 1]
        return logits, cache

    return prefill


# ---------------------------------------------------------------------------
# Decode (serving)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class DecodePolicy:
    """How the KV cache is laid out on the mesh.

    cache_seq_axes: mesh axes sharding the cache sequence dimension.  decode
    shapes use ("model",); the 500k single-sequence shape uses
    ("data", "model") so 256 chips each hold 2k slots.
    batch_axes: axes sharding the decode batch (() when batch == 1).
    """

    cache_seq_axes: tuple[str, ...] = ("model",)
    batch_axes: tuple[str, ...] = ("data",)


def cache_shapes(cfg: TransformerConfig, batch: int, max_seq: int) -> list[tuple]:
    """Per-pattern-entry cache shapes (G, B, S_entry, Hk, dh)."""
    out = []
    for window in cfg.window_pattern:
        s_entry = min(window, max_seq) if window is not None else max_seq
        out.append((cfg.n_groups, batch, s_entry, cfg.n_kv_heads, cfg.d_head))
    return out


def init_cache(cfg: TransformerConfig, batch: int, max_seq: int,
               *, dtype=None) -> dict:
    dtype = dtype or cfg.compute_dtype
    caches = {}
    for i, shape in enumerate(cache_shapes(cfg, batch, max_seq)):
        caches[f"k{i}"] = jnp.zeros(shape, dtype)
        caches[f"v{i}"] = jnp.zeros(shape, dtype)
    return caches


def abstract_cache(cfg: TransformerConfig, batch: int, max_seq: int,
                   *, dtype=None) -> dict:
    dtype = dtype or cfg.compute_dtype
    out = {}
    for i, shape in enumerate(cache_shapes(cfg, batch, max_seq)):
        out[f"k{i}"] = jax.ShapeDtypeStruct(shape, dtype)
        out[f"v{i}"] = jax.ShapeDtypeStruct(shape, dtype)
    return out


def cache_pspecs(cfg: TransformerConfig, policy: ShardingPolicy,
                 decode: DecodePolicy) -> dict:
    seq = decode.cache_seq_axes if len(decode.cache_seq_axes) > 1 else (
        decode.cache_seq_axes[0] if decode.cache_seq_axes else None)
    bat = decode.batch_axes if len(decode.batch_axes) > 1 else (
        decode.batch_axes[0] if decode.batch_axes else None)
    spec = P(None, bat, seq, None, None)
    out = {}
    for i in range(len(cfg.window_pattern)):
        out[f"k{i}"] = spec
        out[f"v{i}"] = spec
    return out


def _decode_attention_sharded(cfg, policy, decode, q, k_cache, v_cache,
                              k_new, v_new, pos, window, max_seq):
    """shard_map decode attention over sequence-sharded cache shards.

    Each shard updates its slice of the ring/global cache if the write index
    lands in range, computes flash-decoding partials over its slots, and the
    partials psum-combine over the cache_seq axes.
    """
    mesh = policy.mesh
    seq_axes = decode.cache_seq_axes
    bat = decode.batch_axes if len(decode.batch_axes) > 1 else (
        decode.batch_axes[0] if decode.batch_axes else None)
    seq = seq_axes if len(seq_axes) > 1 else seq_axes[0]
    cache_spec = P(bat, seq, None, None)   # (B, S, Hk, dh) per layer-slice
    q_spec = P(bat, None, None, None)

    s_entry = k_cache.shape[1]
    n_shards = 1
    for a in seq_axes:
        n_shards *= mesh.shape[a]
    s_loc = s_entry // n_shards

    def local(qs, kc, vc, kn, vn, pos):
        # Flat shard rank across the (possibly multiple) seq axes.
        r = jnp.zeros((), jnp.int32)
        for a in seq_axes:
            r = r * mesh.shape[a] + jax.lax.axis_index(a)
        write_pos = pos % s_entry if window is not None else pos
        w = write_pos - r * s_loc
        in_range = (w >= 0) & (w < s_loc)
        wc = jnp.clip(w, 0, s_loc - 1)
        kc2 = jax.lax.dynamic_update_slice(kc, kn, (0, wc, 0, 0))
        vc2 = jax.lax.dynamic_update_slice(vc, vn, (0, wc, 0, 0))
        kc = jnp.where(in_range, kc2, kc)
        vc = jnp.where(in_range, vc2, vc)
        # Valid slots: global slot index <= pos (or the whole ring once full).
        slots = r * s_loc + jnp.arange(s_loc, dtype=jnp.int32)
        if window is not None:
            valid = (slots <= pos) | (pos >= s_entry)
        else:
            valid = slots <= pos
        mask = jnp.broadcast_to(valid[None], (qs.shape[0], s_loc))
        wv, m, z = attn_lib.decode_attention_partial(
            qs, kc, vc, length_mask=mask, attn_softcap=cfg.attn_softcap)
        out = attn_lib.combine_decode_partials(
            wv, m, z, seq_axes if len(seq_axes) > 1 else seq_axes[0])
        return out.astype(qs.dtype), kc, vc

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(q_spec, cache_spec, cache_spec, q_spec, q_spec, P()),
        out_specs=(q_spec, cache_spec, cache_spec),
        check_vma=False,
    )(q, k_cache, v_cache, k_new, v_new, pos)


def make_serve_step(cfg: TransformerConfig, max_seq: int,
                    *, policy: Optional[ShardingPolicy] = None,
                    decode: DecodePolicy = DecodePolicy()):
    """Returns serve_step(params, cache, tokens (B,1), pos) -> (logits, cache).

    One decode step: append the token's KV at ``pos`` and attend over the
    cache.  MoE layers run the all-expert reference path (DESIGN.md §6).
    """

    def serve_step(params, cache, tokens, pos):
        cdt = cfg.compute_dtype
        b = tokens.shape[0]
        H, Hk, dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
        embed = params["embed"].astype(cdt)
        x = embed[tokens]                                     # (B, 1, d)
        freqs = rope_freqs(cfg.d_head, theta=cfg.rope_theta)
        blocks = [jax.tree_util.tree_map(lambda a: a.astype(cdt), blk)
                  for blk in params["blocks"]]

        def group_body(carry, xs):
            x = carry
            slices, caches = xs
            new_caches = []
            for i, (blk, window) in enumerate(zip(slices, cfg.window_pattern)):
                s_entry = min(window, max_seq) if window is not None else max_seq
                h = rms_norm(x, blk["ln1"])
                q = (h @ blk["wq"]).reshape(b, 1, H, dh)
                kn = (h @ blk["wk"]).reshape(b, 1, Hk, dh)
                vn = (h @ blk["wv"]).reshape(b, 1, Hk, dh)
                posb = jnp.full((b, 1), pos, jnp.int32)
                q = apply_rope(q, posb, freqs)
                kn = apply_rope(kn, posb, freqs)
                kc, vc = caches[2 * i], caches[2 * i + 1]
                if policy is not None:
                    out, kc, vc = _decode_attention_sharded(
                        cfg, policy, decode, q, kc, vc, kn, vn, pos, window, max_seq)
                else:
                    write = pos % s_entry if window is not None else pos
                    kc = jax.lax.dynamic_update_slice(kc, kn, (0, write, 0, 0))
                    vc = jax.lax.dynamic_update_slice(vc, vn, (0, write, 0, 0))
                    slots = jnp.arange(s_entry, dtype=jnp.int32)
                    valid = (slots <= pos) | (jnp.asarray(window is not None) & (pos >= s_entry))
                    mask = jnp.broadcast_to(valid[None], (b, s_entry))
                    out = attn_lib.decode_attention(
                        q, kc, vc, length_mask=mask, attn_softcap=cfg.attn_softcap)
                x = x + out.reshape(b, 1, H * dh) @ blk["wo"]
                # FFN (reference MoE path for decode).
                h2 = rms_norm(x, blk["ln2"])
                if cfg.moe is None:
                    y = (jax.nn.silu(h2 @ blk["w_gate"]) * (h2 @ blk["w_up"])) @ blk["w_down"]
                else:
                    flat = h2.reshape(b, cfg.d_model)
                    y, _ = moe_lib.moe_ffn_reference(blk["moe"], flat, cfg.moe)
                    y = y.reshape(b, 1, cfg.d_model)
                    if cfg.moe.dense_residual_d_ff:
                        y = y + (jax.nn.silu(h2 @ blk["res_gate"]) *
                                 (h2 @ blk["res_up"])) @ blk["res_down"]
                x = x + y
                new_caches.extend([kc, vc])
            return x, tuple(new_caches)

        cache_xs = []
        for i in range(len(cfg.window_pattern)):
            cache_xs.extend([cache[f"k{i}"], cache[f"v{i}"]])
        x, new_cache_xs = jax.lax.scan(group_body, x,
                                       xs=(tuple(blocks), tuple(cache_xs)))
        x = rms_norm(x, params["final_norm"].astype(cdt))
        unembed = (params["embed"].T if cfg.tie_embeddings
                   else params["unembed"]).astype(cdt)
        logits = x @ unembed
        if cfg.final_softcap is not None:
            logits = softcap(logits, cfg.final_softcap)
        new_cache = {}
        for i in range(len(cfg.window_pattern)):
            new_cache[f"k{i}"] = new_cache_xs[2 * i]
            new_cache[f"v{i}"] = new_cache_xs[2 * i + 1]
        return logits[:, 0], new_cache

    return serve_step
