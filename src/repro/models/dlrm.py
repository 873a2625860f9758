"""DLRM (Naumov et al., arXiv:1906.00091), MLPerf Criteo-1TB configuration.

13 dense features -> bottom MLP 512-256-128; 26 categorical features ->
embedding tables (row counts below, dim 128) looked up with an
EmbeddingBag built from ``jnp.take`` + ``jax.ops.segment_sum`` (JAX has no
native EmbeddingBag — the brief makes this lookup part of the system);
pairwise-dot feature interaction over the 27 vectors; top MLP
1024-1024-512-256-1.

Distribution (MLPerf hybrid): tables are model-parallel over the ``model``
axis (row-sharded via shard_map so each lookup routes to the owning shard),
MLPs are data-parallel.  The pooled-embedding all-to-all this produces is
the traffic characterized by
:func:`repro.core.tpu_model.dlrm_embedding_exchange`.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .common import embed_init, mlp_apply, mlp_init
from ..distributed.sharding import ShardingPolicy

Array = jax.Array

# MLPerc Criteo-1TB per-feature cardinalities (day-0..22 preprocessing,
# capped at 40M rows as in the MLPerf reference implementation).
CRITEO_1TB_VOCABS: tuple[int, ...] = (
    40000000, 39060, 17295, 7424, 20265, 3, 7122, 1543, 63, 40000000,
    3067956, 405282, 10, 2209, 11938, 155, 4, 976, 14, 40000000,
    40000000, 40000000, 590152, 12973, 108, 36)


@dataclass(frozen=True)
class DLRMConfig:
    name: str = "dlrm-mlperf"
    n_dense: int = 13
    n_sparse: int = 26
    embed_dim: int = 128
    vocab_sizes: tuple[int, ...] = CRITEO_1TB_VOCABS
    bot_mlp: tuple[int, ...] = (512, 256, 128)
    top_mlp: tuple[int, ...] = (1024, 1024, 512, 256, 1)
    interaction: str = "dot"
    multi_hot: int = 1            # lookups per sparse feature (bag size)

    def __post_init__(self):
        assert len(self.vocab_sizes) == self.n_sparse
        assert self.bot_mlp[-1] == self.embed_dim

    def interaction_dim(self) -> int:
        f = self.n_sparse + 1
        return f * (f - 1) // 2 + self.embed_dim

    def param_count(self) -> int:
        emb = sum(self.vocab_sizes) * self.embed_dim
        bot = sum(a * b + b for a, b in zip((self.n_dense,) + self.bot_mlp[:-1],
                                            self.bot_mlp))
        top_dims = (self.interaction_dim(),) + self.top_mlp
        top = sum(a * b + b for a, b in zip(top_dims[:-1], top_dims[1:]))
        return emb + bot + top


def init_params(cfg: DLRMConfig, rng: Array, *, dtype=jnp.float32) -> dict:
    k_emb, k_bot, k_top = jax.random.split(rng, 3)
    emb_keys = jax.random.split(k_emb, cfg.n_sparse)
    tables = [embed_init(k, (v, cfg.embed_dim), dtype=dtype)
              for k, v in zip(emb_keys, cfg.vocab_sizes)]
    return {
        "tables": tables,
        "bot": mlp_init(k_bot, (cfg.n_dense,) + cfg.bot_mlp, dtype=dtype),
        "top": mlp_init(k_top, (cfg.interaction_dim(),) + cfg.top_mlp, dtype=dtype),
    }


def abstract_params(cfg: DLRMConfig, *, dtype=jnp.float32):
    return jax.eval_shape(lambda k: init_params(cfg, k, dtype=dtype),
                          jax.random.key(0))


def param_pspecs(cfg: DLRMConfig, policy: ShardingPolicy) -> dict:
    """Tables row-sharded over ALL mesh axes where the row count divides
    (the 40M-row Criteo tables shard 512 ways -> ~10 MB/chip instead of
    20 GB replicated); tp-only or replicated as divisibility degrades.
    MLPs are replicated (DP)."""
    tp = policy.tp_axis
    all_axes = tuple(policy.dp_axes) + (tp,)
    n_all = policy.n_devices

    def table_spec(v: int) -> P:
        if v % n_all == 0:
            return P(all_axes, None)
        if v % policy.tp == 0:
            return P(tp, None)
        return P(None, None)

    bot = {"w": [P(None, None)] * len(cfg.bot_mlp),
           "b": [P(None)] * len(cfg.bot_mlp)}
    top = {"w": [P(None, None)] * len(cfg.top_mlp),
           "b": [P(None)] * len(cfg.top_mlp)}
    return {"tables": [table_spec(v) for v in cfg.vocab_sizes],
            "bot": bot, "top": top}


def embedding_bag(table: Array, indices: Array, *, weights: Optional[Array] = None,
                  combine: str = "sum") -> Array:
    """(B, bag) indices -> (B, d) pooled embeddings (take + reduce)."""
    vecs = jnp.take(table, indices, axis=0)          # (B, bag, d)
    if weights is not None:
        vecs = vecs * weights[..., None]
    if combine == "sum":
        return jnp.sum(vecs, axis=1)
    if combine == "mean":
        return jnp.mean(vecs, axis=1)
    raise ValueError(combine)


def dot_interaction(vectors: Array) -> Array:
    """(B, F, d) -> (B, F*(F-1)/2) lower-triangle pairwise dots."""
    b, f, d = vectors.shape
    prods = jnp.einsum("bfd,bgd->bfg", vectors, vectors)
    iu, ju = jnp.tril_indices(f, k=-1)
    return prods[:, iu, ju]


def vocab_parallel_embeddings(cfg: DLRMConfig, tables: Sequence[Array],
                              sparse: Array, policy: ShardingPolicy) -> Array:
    """Row-sharded embedding-bag: masked local lookup + psum over the table
    shards (Megatron vocab-parallel pattern).  Big tables shard over ALL
    mesh axes (the lookup batch is replicated during the embedding stage);
    non-divisible tables degrade to tp-only or replicated.  Returns
    (B, n_sparse, d), replicated.

    Traffic: one all-reduce of (B, n_sharded_tables, d) per step — the DLRM
    analogue of the paper's loadvert terms; modeled by
    :func:`repro.core.tpu_model.dlrm_embedding_exchange` (a2a variant is the
    §Perf optimization).
    """
    tp, tp_size = policy.tp_axis, policy.tp
    all_axes = tuple(policy.dp_axes) + (tp,)
    n_all = policy.n_devices

    def shards_of(v: int) -> int:
        if v % n_all == 0:
            return n_all
        if v % tp_size == 0:
            return tp_size
        return 1

    specs = []
    for v in cfg.vocab_sizes:
        s = shards_of(v)
        specs.append(P(all_axes, None) if s == n_all
                     else P(tp, None) if s == tp_size else P(None, None))

    def local(tables_loc, sparse_rep):
        outs = [None] * cfg.n_sparse
        r_all = jnp.zeros((), jnp.int32)
        for a in all_axes:
            r_all = r_all * policy.mesh.shape[a] + jax.lax.axis_index(a)
        r_tp = jax.lax.axis_index(tp)
        partials_all, idx_all = [], []
        partials_tp, idx_tp = [], []
        for t, (tab, v) in enumerate(zip(tables_loc, cfg.vocab_sizes)):
            idx = sparse_rep[:, t, :]
            s = shards_of(v)
            if s == 1:
                outs[t] = jnp.sum(jnp.take(tab, idx, axis=0), axis=1)
                continue
            rows = v // s
            r = r_all if s == n_all else r_tp
            loc = idx - r * rows
            ok = (loc >= 0) & (loc < rows)
            vecs = jnp.take(tab, jnp.clip(loc, 0, rows - 1), axis=0)
            pooled = jnp.sum(vecs * ok[..., None], axis=1)
            if s == n_all:
                partials_all.append(pooled)
                idx_all.append(t)
            else:
                partials_tp.append(pooled)
                idx_tp.append(t)
        if partials_all:
            red = jax.lax.psum(jnp.stack(partials_all, 1), all_axes)
            for j, t in enumerate(idx_all):
                outs[t] = red[:, j]
        if partials_tp:
            red = jax.lax.psum(jnp.stack(partials_tp, 1), tp)
            # still differs across dp groups? no: sparse is replicated, and
            # tp-sharded tables psum over tp give identical values on every
            # dp rank.
            for j, t in enumerate(idx_tp):
                outs[t] = red[:, j]
        return jnp.stack(outs, axis=1)

    return jax.shard_map(
        local, mesh=policy.mesh,
        in_specs=(specs, P(None, None, None)),   # batch replicated for lookup
        out_specs=P(None, None, None),
        check_vma=False,
    )(list(tables), sparse)


def forward(cfg: DLRMConfig, params: dict, batch: dict,
            *, policy: Optional[ShardingPolicy] = None) -> Array:
    """batch: dense (B, 13) float; sparse (B, 26, multi_hot) int32 -> logits (B,)."""
    dense, sparse = batch["dense"], batch["sparse"]
    b = dense.shape[0]
    bot = mlp_apply(params["bot"], dense, final_act=True)    # (B, d)
    if policy is not None:
        emb = vocab_parallel_embeddings(cfg, params["tables"], sparse, policy)
    else:
        pooled = []
        for t, table in enumerate(params["tables"]):
            pooled.append(embedding_bag(table, sparse[:, t, :]))
        emb = jnp.stack(pooled, axis=1)                      # (B, 26, d)
    feats = jnp.concatenate([bot[:, None, :], emb], axis=1)  # (B, 27, d)
    if policy is not None:
        # Re-shard the batch over ALL axes for the interaction + top MLP so
        # the dense compute is data-parallel across the whole mesh.
        all_axes = tuple(policy.dp_axes) + (policy.tp_axis,)
        feats = policy.constrain(feats, P(all_axes, None, None))
        bot = policy.constrain(bot, P(all_axes, None))
    inter = dot_interaction(feats)
    top_in = jnp.concatenate([bot, inter], axis=-1)
    return mlp_apply(params["top"], top_in)[:, 0]


def loss_fn(cfg: DLRMConfig, params: dict, batch: dict,
            *, policy: Optional[ShardingPolicy] = None) -> tuple[Array, dict]:
    logits = forward(cfg, params, batch, policy=policy)
    labels = batch["labels"].astype(jnp.float32)
    logp = jax.nn.log_sigmoid(logits)
    lognp = jax.nn.log_sigmoid(-logits)
    loss = -jnp.mean(labels * logp + (1 - labels) * lognp)
    acc = jnp.mean((logits > 0) == (labels > 0.5))
    return loss, {"loss": loss, "acc": acc}


def score_candidates(cfg: DLRMConfig, params: dict, query: dict,
                     candidates: Array) -> Array:
    """Retrieval scoring: one query's user vector dotted against (Nc, d)
    candidate item embeddings — a batched matvec, not a loop."""
    bot = mlp_apply(params["bot"], query["dense"], final_act=True)  # (1, d)
    return (candidates @ bot[0]).astype(jnp.float32)                # (Nc,)
