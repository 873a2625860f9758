"""Substrate tests: checkpointing (atomic, retention, elastic), resilience
(fault injection + recovery), gradient compression, neighbor sampler,
optimizers."""

import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from hypothesis import given, settings, strategies as st

from repro.checkpoint.manager import CheckpointManager
from repro.data.sampler import build_csr, sample_subgraph
from repro.data import synthetic
from repro.distributed.resilience import (FaultInjector, StepMonitor,
                                          WorkerFailure, run_resilient)
from repro.optim.compression import compress_decompress, wrap_optimizer
from repro.optim.optimizers import adamw, apply_updates, global_norm, sgd


# ---------------------------------------------------------------------------
# Checkpointing
# ---------------------------------------------------------------------------

def _tree(seed=0):
    k = jax.random.key(seed)
    return {"w": jax.random.normal(k, (8, 4)),
            "b": jnp.arange(4.0),
            "nested": {"s": jnp.asarray(3, jnp.int32)}}


def test_checkpoint_roundtrip(tmp_path):
    mgr = CheckpointManager(tmp_path, keep=2)
    t = _tree()
    mgr.save(7, t)
    step, restored = mgr.restore(jax.tree_util.tree_map(jnp.zeros_like, t))
    assert step == 7
    for a, b in zip(jax.tree_util.tree_leaves(t),
                    jax.tree_util.tree_leaves(restored)):
        assert jnp.allclose(a, b)


def test_checkpoint_retention_and_latest(tmp_path):
    mgr = CheckpointManager(tmp_path, keep=2)
    for s in (1, 2, 3, 4):
        mgr.save(s, _tree(s))
    assert mgr.steps() == [3, 4]
    assert mgr.latest_step() == 4


def test_checkpoint_structure_mismatch_raises(tmp_path):
    mgr = CheckpointManager(tmp_path)
    mgr.save(1, _tree())
    with pytest.raises(ValueError):
        mgr.restore({"only": jnp.zeros((2,))})


def test_checkpoint_tmp_never_visible(tmp_path):
    mgr = CheckpointManager(tmp_path)
    mgr.save(5, _tree())
    assert not list(Path(tmp_path).glob("*.tmp"))


def test_elastic_restore_subprocess(tmp_path):
    """Save on 1 device, restore sharded onto an 8-device mesh."""
    mgr = CheckpointManager(tmp_path)
    mgr.save(3, {"w": jnp.arange(64.0).reshape(8, 8)})
    code = f"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.checkpoint.manager import CheckpointManager
mesh = jax.make_mesh((8,), ("x",), axis_types=(jax.sharding.AxisType.Auto,))
mgr = CheckpointManager({str(tmp_path)!r})
step, out = mgr.restore({{"w": jnp.zeros((8, 8))}},
                        shardings={{"w": NamedSharding(mesh, P("x", None))}})
assert step == 3
assert len(out["w"].sharding.device_set) == 8
assert float(out["w"].sum()) == float(sum(range(64)))
print("ELASTIC OK")
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = str(Path(__file__).resolve().parents[1] / "src")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr
    assert "ELASTIC OK" in proc.stdout


# ---------------------------------------------------------------------------
# Resilience
# ---------------------------------------------------------------------------

def test_resilient_loop_recovers_from_faults(tmp_path):
    mgr = CheckpointManager(tmp_path, keep=3)
    calls = []

    def step_fn(state, batch):
        calls.append(batch)
        return state + 1, {"loss": float(100 - state)}

    inj = FaultInjector(frozenset({7, 13}))
    state, hist = run_resilient(
        state=jnp.asarray(0), step_fn=step_fn, batch_fn=lambda s: s,
        n_steps=20, checkpoint_manager=mgr, checkpoint_every=5,
        injector=inj, log_every=0)
    assert int(state) == 20
    assert [h["step"] for h in hist][-1] == 19
    assert mgr.latest_step() == 20


def test_resilient_loop_gives_up_after_max_restarts(tmp_path):
    class AlwaysFail(FaultInjector):
        def check(self, step):
            if step == 2:
                raise WorkerFailure("persistent fault")

    with pytest.raises(WorkerFailure):
        run_resilient(state=jnp.asarray(0),
                      step_fn=lambda s, b: (s + 1, {}),
                      batch_fn=lambda s: None, n_steps=5,
                      checkpoint_manager=CheckpointManager(tmp_path),
                      checkpoint_every=100, injector=AlwaysFail(),
                      max_restarts=2, log_every=0)


def test_straggler_monitor_flags_slow_steps():
    mon = StepMonitor(threshold=2.0)
    for s in range(10):
        mon.observe(s, 0.1)
    assert mon.observe(10, 1.0)
    assert mon.stragglers == [10]


# ---------------------------------------------------------------------------
# Gradient compression
# ---------------------------------------------------------------------------

def test_compression_error_feedback_contracts():
    g = jnp.asarray(np.random.default_rng(0).standard_normal(1000), jnp.float32)
    err = jnp.zeros(1000)
    deq, err2 = compress_decompress(g, err)
    # int8 quantization error bounded by scale/2 per element
    scale = float(jnp.max(jnp.abs(g))) / 127.0
    assert float(jnp.max(jnp.abs(err2))) <= scale * 0.5 + 1e-6
    # error feedback: accumulated (deq + err2) == original
    assert jnp.allclose(deq + err2, g, atol=1e-6)


def test_compressed_training_converges_like_uncompressed():
    """Least squares with/without compression reach similar loss."""
    rng = np.random.default_rng(0)
    A = jnp.asarray(rng.standard_normal((64, 8)), jnp.float32)
    y = jnp.asarray(rng.standard_normal(64), jnp.float32)

    def loss(w):
        return jnp.mean((A @ w - y) ** 2)

    def run(opt):
        w = jnp.zeros(8)
        st = opt.init(w)
        for _ in range(200):
            g = jax.grad(loss)(w)
            up, st = opt.update(g, st, w)
            w = apply_updates(w, up)
        return float(loss(w))

    plain = run(sgd(0.05, momentum=0.0))
    comp = run(wrap_optimizer(sgd(0.05, momentum=0.0)))
    assert comp < plain * 1.2 + 1e-3, (plain, comp)


# ---------------------------------------------------------------------------
# Neighbor sampler
# ---------------------------------------------------------------------------

def test_sampler_respects_fanout_and_membership():
    rng = np.random.default_rng(0)
    ga = synthetic.power_law_graph(0, n_nodes=500, n_edges=4000, d_feat=4,
                                   self_loops=False)
    csr = build_csr(ga.senders, ga.receivers, 500)
    seeds = rng.choice(500, 32, replace=False)
    sub = sample_subgraph(csr, seeds, (5, 3), rng=rng, n_pad=1024, e_pad=1024)
    assert sub.n_real_nodes <= 32 + 32 * 5 + 32 * 5 * 3
    assert sub.n_real_edges <= 32 * 5 + 32 * 5 * 3
    # every sampled edge exists in the original graph
    edge_set = set(zip(ga.senders.tolist(), ga.receivers.tolist()))
    for i in range(sub.n_real_edges):
        s_g = int(sub.node_ids[sub.senders[i]])
        r_g = int(sub.node_ids[sub.receivers[i]])
        assert (s_g, r_g) in edge_set
    # seeds are the first nodes and flagged by seed_mask
    assert np.array_equal(sub.node_ids[:32], seeds)
    assert sub.seed_mask[:32].sum() == 32


def test_sampler_determinism():
    ga = synthetic.power_law_graph(1, n_nodes=300, n_edges=2000, d_feat=4)
    csr = build_csr(ga.senders, ga.receivers, 300)
    seeds = np.arange(16)
    s1 = sample_subgraph(csr, seeds, (4, 2),
                         rng=np.random.default_rng(42), n_pad=512, e_pad=512)
    s2 = sample_subgraph(csr, seeds, (4, 2),
                         rng=np.random.default_rng(42), n_pad=512, e_pad=512)
    assert np.array_equal(s1.senders, s2.senders)
    assert np.array_equal(s1.node_ids, s2.node_ids)


# ---------------------------------------------------------------------------
# Optimizers
# ---------------------------------------------------------------------------

def test_adamw_reduces_quadratic():
    def loss(w):
        return jnp.sum((w - 3.0) ** 2)

    opt = adamw(0.1)
    w = jnp.zeros(4)
    st = opt.init(w)
    for _ in range(100):
        up, st = opt.update(jax.grad(loss)(w), st, w)
        w = apply_updates(w, up)
    assert float(loss(w)) < 0.05


@settings(max_examples=20, deadline=None)
@given(st.floats(0.1, 10.0))
def test_clip_bounds_global_norm(max_norm):
    from repro.optim.optimizers import clip_by_global_norm
    g = {"a": jnp.full((10,), 5.0), "b": jnp.full((3, 3), -2.0)}
    clipped, norm = clip_by_global_norm(g, max_norm)
    assert float(global_norm(clipped)) <= max_norm * 1.001


def test_synthetic_determinism():
    b1 = synthetic.lm_batch(0, 5, batch=2, seq=8, vocab=100)
    b2 = synthetic.lm_batch(0, 5, batch=2, seq=8, vocab=100)
    assert np.array_equal(b1["tokens"], b2["tokens"])
    b3 = synthetic.criteo_batch(0, 5, batch=4, n_dense=13,
                                vocab_sizes=(10, 20, 30))
    b4 = synthetic.criteo_batch(0, 5, batch=4, n_dense=13,
                                vocab_sizes=(10, 20, 30))
    assert np.array_equal(b3["sparse"], b4["sparse"])
    assert (b3["sparse"] < np.array([10, 20, 30])[None, :, None]).all()
