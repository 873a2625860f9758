"""Training driver: config -> data -> resilient loop -> checkpoints.

CPU-runnable with the smoke configs (this is what examples/ call); on a pod
the same driver runs the full configs with the production mesh by passing
``--full --mesh single|multi`` (the step functions are identical to the
dry-run's).

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch smollm-135m \
      --steps 200 --batch 8 --seq 128
  PYTHONPATH=src python -m repro.launch.train --arch gcn-cora --steps 300
"""

from __future__ import annotations

import argparse
import logging
import tempfile
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..checkpoint.manager import CheckpointManager
from ..configs import get_arch
from ..data import synthetic
from ..distributed.resilience import FaultInjector, StepMonitor, run_resilient
from ..models import dlrm as dlrm_lib
from ..models import transformer as tf_lib
from ..models.gnn import equiformer_v2 as eqv2_lib
from ..models.gnn import gatedgcn as ggcn_lib
from ..models.gnn import gcn as gcn_lib
from ..models.gnn import meshgraphnet as mgn_lib
from ..models.gnn.graph import GraphBatch
from ..optim.optimizers import adamw, apply_updates, cosine_schedule
from ..optim import compression

logger = logging.getLogger("repro.train")

_GNN_MODULES = {"gcn-cora": gcn_lib, "gatedgcn": ggcn_lib,
                "meshgraphnet": mgn_lib, "equiformer-v2": eqv2_lib}


def _lm_setup(arch, args):
    cfg = (arch.make_config() if args.full else arch.make_smoke_config())
    params = tf_lib.init_params(cfg, jax.random.key(args.seed))
    optimizer = adamw(cosine_schedule(args.lr, warmup=20, total=args.steps),
                      weight_decay=0.1)
    if args.compress_grads:
        optimizer = compression.wrap_optimizer(optimizer)
    opt_state = optimizer.init(params)
    train_step = jax.jit(tf_lib.make_train_step(cfg, optimizer))

    def step_fn(state, batch):
        params, opt_state = state
        params, opt_state, metrics = train_step(params, opt_state, batch)
        return (params, opt_state), metrics

    def batch_fn(step):
        b = synthetic.lm_batch(args.seed, step, batch=args.batch,
                               seq=args.seq, vocab=cfg.vocab)
        return {k: jnp.asarray(v) for k, v in b.items()}

    return (params, opt_state), step_fn, batch_fn


def _gnn_setup(arch, args):
    cfg = arch.make_smoke_config() if not args.full else arch.make_config()
    module = _GNN_MODULES[arch.name]
    params = module.init_params(cfg, jax.random.key(args.seed))
    optimizer = adamw(args.lr)
    opt_state = optimizer.init(params)

    # One fixed synthetic graph (full-batch training semantics).
    d_in = cfg.d_in
    n_classes = getattr(cfg, "n_classes", 3)
    ga = synthetic.power_law_graph(
        args.seed, n_nodes=args.gnn_nodes, n_edges=args.gnn_edges,
        d_feat=d_in, n_classes=n_classes,
        self_loops=arch.name != "equiformer-v2")
    kw = dict(node_feat=jnp.asarray(ga.node_feat),
              senders=jnp.asarray(ga.senders),
              receivers=jnp.asarray(ga.receivers))
    if arch.name == "gatedgcn":
        kw["edge_feat"] = jnp.ones((ga.n_edges, cfg.d_edge_in), jnp.float32)
        kw["labels"] = jnp.asarray(ga.labels)
    elif arch.name == "meshgraphnet":
        kw["edge_feat"] = jnp.ones((ga.n_edges, cfg.d_edge_in), jnp.float32)
        rng = np.random.default_rng(args.seed)
        kw["labels"] = jnp.asarray(
            rng.standard_normal((ga.n_nodes, cfg.d_out)), jnp.float32)
    elif arch.name == "equiformer-v2":
        from ..data.wigner import rotation_to_z, wigner_stack
        rng = np.random.default_rng(args.seed)
        pos = rng.standard_normal((ga.n_nodes, 3))
        vecs = pos[ga.senders] - pos[ga.receivers]
        Rs = np.stack([rotation_to_z(v) for v in vecs])
        wig = wigner_stack(Rs, cfg.l_max, m_max=cfg.m_max)
        kw["wigner"] = {l: jnp.asarray(w) for l, w in wig.items()}
        kw["positions"] = jnp.asarray(pos, jnp.float32)
        kw["labels"] = jnp.asarray(rng.standard_normal((1, cfg.d_out)), jnp.float32)
    else:
        kw["labels"] = jnp.asarray(ga.labels)
    g = GraphBatch(**kw)

    loss_fn = partial(module.loss_fn, cfg)

    @jax.jit
    def train_step(params, opt_state, g):
        (loss, metrics), grads = jax.value_and_grad(
            lambda p: loss_fn(p, g), has_aux=True)(params)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        return apply_updates(params, updates), opt_state, metrics

    def step_fn(state, batch):
        params, opt_state = state
        params, opt_state, metrics = train_step(params, opt_state, batch)
        return (params, opt_state), metrics

    return (params, opt_state), step_fn, lambda step: g


def _dlrm_setup(arch, args):
    cfg = arch.make_smoke_config() if not args.full else arch.make_config()
    params = dlrm_lib.init_params(cfg, jax.random.key(args.seed))
    optimizer = adamw(args.lr)
    opt_state = optimizer.init(params)

    @jax.jit
    def train_step(params, opt_state, batch):
        (loss, metrics), grads = jax.value_and_grad(
            lambda p: dlrm_lib.loss_fn(cfg, p, batch), has_aux=True)(params)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        return apply_updates(params, updates), opt_state, metrics

    def step_fn(state, batch):
        params, opt_state = state
        params, opt_state, metrics = train_step(params, opt_state, batch)
        return (params, opt_state), metrics

    def batch_fn(step):
        b = synthetic.criteo_batch(args.seed, step, batch=args.batch,
                                   n_dense=cfg.n_dense,
                                   vocab_sizes=cfg.vocab_sizes,
                                   multi_hot=cfg.multi_hot)
        return {k: jnp.asarray(v) for k, v in b.items()}

    return (params, opt_state), step_fn, batch_fn


def run(args) -> list[dict]:
    arch = get_arch(args.arch)
    setup = {"lm": _lm_setup, "gnn": _gnn_setup, "recsys": _dlrm_setup}[arch.family]
    state, step_fn, batch_fn = setup(arch, args)
    ckpt = CheckpointManager(args.ckpt_dir or tempfile.mkdtemp(prefix="repro_ckpt_"),
                             keep=3)
    injector = FaultInjector(frozenset(args.fail_at or []))
    state, history = run_resilient(
        state=state, step_fn=step_fn, batch_fn=batch_fn, n_steps=args.steps,
        checkpoint_manager=ckpt, checkpoint_every=args.checkpoint_every,
        injector=injector, monitor=StepMonitor())
    if history:
        first, last = history[0], history[-1]
        logger.info("loss: %.4f -> %.4f over %d steps",
                    first.get("loss", float("nan")),
                    last.get("loss", float("nan")), len(history))
    return history


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--full", action="store_true",
                    help="use the full published config (pod-scale)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--checkpoint-every", type=int, default=50)
    ap.add_argument("--fail-at", type=int, nargs="*", default=None,
                    help="inject worker failures at these steps")
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--gnn-nodes", type=int, default=256)
    ap.add_argument("--gnn-edges", type=int, default=1024)
    return ap


def main() -> None:
    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(message)s")
    run(build_parser().parse_args())


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
