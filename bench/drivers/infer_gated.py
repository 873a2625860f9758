"""GatedGCN inference over one PATTERN batch, in a closed loop.

One forward is ``repro.models.gnn.gatedgcn.forward`` at the
configuration's published widths with ``edge_layer_fn =
repro.kernels.ops.gated_edge_layer``, so each of its layers runs the fused
gated edge kernel over the batch's receiver-tiled layout, jitted as one
program with every float32 product at ``Precision.HIGHEST``. The batch
is the one ``graph_seed`` fixes (``patterngen.py``); the weights and the
BatchNorm statistics are drawn from ``--seed`` by the program's
``init_params`` on the host's CPU backend, whose compile takes seconds
where the chip's takes over a minute cold, and then moved to the chip. The layout
is built once in set-up, and its counters kept for the metrics. The
window runs forwards back to back, each waited for, and ``infer_ms`` is
the window over the forwards done.

Correct: the last forward's logits against the float64 reference
(``gated_reference.gatedgcn_forward``), as the largest absolute error
over the largest reference magnitude, within the configuration's
``forward_rel_err_limit``.
"""

from __future__ import annotations

import functools
import time

import numpy as np

import gated_reference
import patterngen


def seed_key(seed: int) -> int:
    return int(np.random.SeedSequence([int(seed), 0x6A7E]).generate_state(1)[0]
               & 0x7FFFFFFF)


def model_config(cfg: dict):
    """The program's model configuration for this benchmark configuration."""
    from repro.configs.gatedgcn import make_pattern_config

    return make_pattern_config(
        n_layers=cfg["n_layers"], d_hidden=cfg["d_hidden"],
        d_in=cfg["node_vocab"], d_edge_in=cfg["d_edge_in"],
        n_classes=cfg["n_classes"],
        readout_hidden=tuple(cfg["readout_hidden"]))


def program_forward(mcfg, params, g, layout):
    """The system under test: the model's forward through the kernel."""
    import jax

    from repro.kernels import ops
    from repro.models.gnn import gatedgcn

    with jax.default_matmul_precision("highest"):
        return gatedgcn.forward(mcfg, params, g, remat=False,
                                edge_layer_fn=ops.gated_edge_layer,
                                edge_layout=layout)


class Driver:
    def __init__(self, ctx) -> None:
        import jax
        import jax.numpy as jnp

        from repro.kernels import gated_edge
        from repro.models.gnn import gatedgcn
        from repro.models.gnn.graph import GraphBatch

        cfg = self.cfg = ctx.config
        mcfg = model_config(cfg)
        t0 = time.perf_counter()
        snd, rcv, gid, labels = patterngen.pattern_batch(cfg)
        self.batch = (snd, rcv, labels, gid)
        self.g = GraphBatch(
            node_feat=jnp.asarray(labels), senders=jnp.asarray(snd),
            receivers=jnp.asarray(rcv), graph_ids=jnp.asarray(gid),
            n_graphs=cfg["n_graphs"])
        gated_edge.reset_layout_stats()
        self.layout = gated_edge.build_layout(
            snd, rcv, cfg["n_nodes"], gid, block_edges=cfg["block_edges"])
        self.layout_stats = gated_edge.layout_stats()
        try:
            host = jax.devices("cpu")[0]
        except RuntimeError:      # JAX_PLATFORMS without the CPU backend
            host = jax.devices()[0]
        with jax.default_device(host):
            params = jax.jit(functools.partial(gatedgcn.init_params, mcfg))(
                jax.random.PRNGKey(seed_key(ctx.seed)))
        self.params = jax.device_put(params, jax.devices()[0])
        jax.block_until_ready((self.g, self.layout, self.params))
        ctx.note("inputs_s", time.perf_counter() - t0)
        for key, value in self.layout_stats.items():
            ctx.note(f"layout_{key}" if key != "layout_ns" else key, value)
        t0 = time.perf_counter()
        self.forward = jax.jit(functools.partial(program_forward, mcfg))
        for _ in range(3):
            self.forward(self.params, self.g, self.layout).block_until_ready()
        ctx.note("warmup_s", time.perf_counter() - t0)

    def window(self, seconds: float) -> dict:
        import jax

        n = 0
        t0 = time.perf_counter()
        while True:
            with jax.profiler.TraceAnnotation("bench.infer.forward"):
                out = self.forward(self.params, self.g, self.layout)
                out.block_until_ready()
            n += 1
            if time.perf_counter() - t0 >= seconds:
                break
        elapsed = time.perf_counter() - t0
        cfg = self.cfg
        return {"metrics": {"infer_ms": elapsed / n * 1e3},
                "attempted": n, "elapsed_s": elapsed, "forwards": n,
                "layout": dict(self.layout_stats),
                "edges": cfg["n_edges"], "nodes": cfg["n_nodes"],
                "out": np.asarray(out),
                "params": jax.tree_util.tree_map(np.asarray, self.params)}

    def release(self) -> None:
        self.g = self.layout = self.params = None

    def verify(self, record: dict):
        err = forward_error(record["out"], record["params"], self.batch,
                            self.cfg)
        limit = self.cfg["forward_rel_err_limit"]
        checks = {"forward_rel_err": {"value": err, "limit": limit}}
        return checks, int(not err <= limit)


def forward_error(out, params, batch, cfg: dict) -> float:
    """Largest absolute error of ``out`` over the largest magnitude of the
    float64 reference."""
    snd, rcv, labels, gid = batch
    if out.shape != (cfg["n_nodes"], cfg["n_classes"]):
        return float("inf")
    ref = gated_reference.gatedgcn_forward(params, labels, snd, rcv, gid)
    err = float(np.max(np.abs(np.asarray(out, np.float64) - ref)))
    err /= float(np.max(np.abs(ref)))
    return err if np.isfinite(err) else float("inf")
