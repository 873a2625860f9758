"""Full-graph GCN inference through the fused kernel, in a closed loop.

One forward is ``Â relu(Â X W1) W2``, each layer one
``repro.kernels.ops.gnn_aggregate_combine`` call, jitted as one program.
Â is the symmetric-normalised adjacency with self-loops of the
configuration's graph, dense and zero-padded to a multiple of
``pad_multiple`` nodes; X, W1 and W2 are float32 normals made on the
device from the seed in one call. The window runs forwards back to back,
each waited for, and ``infer_ms`` is the window over the forwards done.

Correct: the last forward's output, against the float64 reference
forward over the edge list (``reference.gcn_forward``), as the largest
absolute error over the largest reference magnitude, within the
configuration's ``forward_rel_err_limit``; padded rows must stay zero.
"""

from __future__ import annotations

import time

import numpy as np

import graphs
import reference


def seed_key(seed: int) -> int:
    return int(np.random.SeedSequence([int(seed), 0x1F]).generate_state(1)[0]
               & 0x7FFFFFFF)


def program_forward(a, x, w1, w2):
    """The system under test: two fused aggregate-combine layers."""
    import jax

    from repro.kernels import ops

    h = jax.nn.relu(ops.gnn_aggregate_combine(a, x, w1))
    return ops.gnn_aggregate_combine(a, h, w2)


class Driver:
    def __init__(self, ctx) -> None:
        import jax
        import jax.numpy as jnp

        cfg = ctx.config
        self.cfg = cfg
        self.data_dir = ctx.data_dir
        n, mult = cfg["n_nodes"], cfg["pad_multiple"]
        self.n_pad = -(-n // mult) * mult
        widths = (cfg["d_in"], cfg["d_hidden"], cfg["n_classes"])

        @jax.jit
        def make_inputs(key):
            k1, k2, k3 = jax.random.split(key, 3)
            rows = jnp.arange(self.n_pad)[:, None] < n
            x = jnp.where(rows, jax.random.normal(
                k1, (self.n_pad, widths[0]), jnp.float32), 0.0)
            w1 = jax.random.normal(k2, widths[:2], jnp.float32)
            w2 = jax.random.normal(k3, widths[1:], jnp.float32)
            return (x, w1 / np.sqrt(widths[0]).astype(np.float32),
                    w2 / np.sqrt(widths[1]).astype(np.float32))

        t0 = time.perf_counter()
        snd, rcv = graphs.graph_arrays(cfg, ctx.data_dir)
        self.a = jax.device_put(
            graphs.dense_gcn_adjacency(snd, rcv, n, self.n_pad))
        self.x, self.w1, self.w2 = make_inputs(
            jax.random.PRNGKey(seed_key(ctx.seed)))
        jax.block_until_ready((self.a, self.x, self.w1, self.w2))
        ctx.note("inputs_s", time.perf_counter() - t0)
        t0 = time.perf_counter()
        self.forward = jax.jit(program_forward)
        for _ in range(3):
            self.forward(self.a, self.x, self.w1, self.w2).block_until_ready()
        ctx.note("warmup_s", time.perf_counter() - t0)
        self.nnz = int(np.asarray(rcv).size) + n
        ctx.note("padded_nodes", self.n_pad)
        ctx.note("adjacency_nonzeros", self.nnz)

    def window(self, seconds: float) -> dict:
        import jax

        args = (self.a, self.x, self.w1, self.w2)
        n = 0
        t0 = time.perf_counter()
        while True:
            with jax.profiler.TraceAnnotation("bench.infer.forward"):
                out = self.forward(*args)
                out.block_until_ready()
            n += 1
            if time.perf_counter() - t0 >= seconds:
                break
        elapsed = time.perf_counter() - t0
        return {"metrics": {"infer_ms": elapsed / n * 1e3},
                "attempted": n, "elapsed_s": elapsed, "forwards": n,
                "nnz": self.nnz, "out": np.asarray(out),
                "inputs": [np.asarray(v) for v in (self.x, self.w1, self.w2)]}

    def release(self) -> None:
        self.a = self.x = self.w1 = self.w2 = None

    def verify(self, record: dict):
        err = forward_error(record["out"], record["inputs"], self.cfg,
                            self.data_dir, self.n_pad)
        limit = self.cfg["forward_rel_err_limit"]
        checks = {"forward_rel_err": {"value": err, "limit": limit}}
        return checks, int(not err <= limit)


def forward_error(out, inputs, cfg: dict, data_dir, n_pad: int) -> float:
    """Largest absolute error of ``out`` over the largest magnitude of the
    float64 reference; padded rows are held to zero."""
    n = cfg["n_nodes"]
    if out.shape != (n_pad, cfg["n_classes"]):
        return float("inf")
    snd, rcv = graphs.graph_arrays(cfg, data_dir)
    x, w1, w2 = inputs
    ref = reference.gcn_forward(x, w1, w2, snd, rcv, n)
    got = np.asarray(out, np.float64)
    err = max(float(np.max(np.abs(got[:n] - ref))),
              float(np.max(np.abs(got[n:]), initial=0.0)))
    err /= float(np.max(np.abs(ref)))
    return err if np.isfinite(err) else float("inf")
