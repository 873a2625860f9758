"""Whole runs at small sizes on the CPU, skipping only the look for a chip:
sound runs come out correct, and the controls and planted faults in the
timed path come out not correct."""

import time

import jax
import numpy as np
import pytest

import controls
import harness

SMALL = {
    "products.sweep": {"name": "small-products", "n_nodes": 20_000,
                       "n_edges": 200_000, "n_undirected_edges": 100_000},
    "cora.sweep": {"name": "small-cora", "n_nodes": 700, "n_edges": 2_800,
                   "n_undirected_edges": 1_400},
    "cora.infer": {"name": "small-cora", "n_nodes": 700, "n_edges": 2_800,
                   "n_undirected_edges": 1_400, "d_in": 384},
}
TRAFFIC = {"products.sweep": {"octaves": [6, 8, 10, 12]}}


def run(cell, tmp_path, seed=12_345_678_901, hook=None):
    overrides = {"config": SMALL[cell], "traffic": TRAFFIC.get(cell, {}),
                 "data_dir": str(tmp_path / "data")}
    return harness.run_cell(cell, seed, 0.3, False,
                            t_start=time.perf_counter(), require_tpu=False,
                            overrides=overrides, driver_hook=hook)


@pytest.mark.parametrize("cell", sorted(SMALL))
def test_sound_run_is_correct(cell, scratch_env):
    result = run(cell, scratch_env)
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert "setup_s" in result["metrics"]
    assert list(result)[-2:] == ["checks", "_info"]


@pytest.mark.parametrize("cell", sorted(SMALL))
def test_control_is_not_correct(cell, scratch_env):
    kind = "infer" if cell.endswith("infer") else "sweep"
    result = run(cell, scratch_env, hook=controls.install(kind))
    assert not result["correct"], result["checks"]


def _altered_counts(monkeypatch):
    from repro.kernels import segment_reduce

    orig = segment_reduce.schedule_counts

    def altered(*args):
        halo, cut = orig(*args)
        return halo.at[0].add(1), cut

    monkeypatch.setattr(segment_reduce, "schedule_counts", altered)


def _half_the_pairs(monkeypatch):
    from repro.kernels import segment_reduce

    orig = segment_reduce.schedule_counts

    def half(u_snd, u_rcv, u_new_src, mult, K, n_pad):
        h = len(u_snd) // 2
        return orig(u_snd[:h], u_rcv[:h], u_new_src[:h], mult[:h], K, n_pad)

    monkeypatch.setattr(segment_reduce, "schedule_counts", half)


def _state_unchanged(monkeypatch):
    from repro.core.trace import GraphTrace

    monkeypatch.setattr(GraphTrace, "clear_schedules", lambda self: None)


def _altered_output(monkeypatch):
    from repro.kernels import ops

    orig = ops.gnn_aggregate_combine

    def altered(a, x, w):
        out = orig(a, x, w)
        return out.at[0, 0].add(1e-3 * jax.numpy.max(jax.numpy.abs(out)))

    monkeypatch.setattr(ops, "gnn_aggregate_combine", altered)


def _half_the_sources(monkeypatch):
    from repro.kernels import ops

    orig = ops.gnn_aggregate_combine

    def half(a, x, w):
        return orig(a.at[:, a.shape[1] // 2:].set(0.0), x, w)

    monkeypatch.setattr(ops, "gnn_aggregate_combine", half)


SWEEP_FAULTS = {"altered_count": _altered_counts,
                "half_the_pairs": _half_the_pairs,
                "state_unchanged": _state_unchanged}
INFER_FAULTS = {"altered_output": _altered_output,
                "half_the_sources": _half_the_sources}


@pytest.mark.parametrize("cell", ["cora.sweep", "products.sweep"])
@pytest.mark.parametrize("fault", sorted(SWEEP_FAULTS))
def test_sweep_fault_is_not_correct(cell, fault, scratch_env, monkeypatch):
    SWEEP_FAULTS[fault](monkeypatch)
    result = run(cell, scratch_env)
    assert not result["correct"], result["checks"]


@pytest.mark.parametrize("fault", sorted(INFER_FAULTS))
def test_infer_fault_is_not_correct(fault, scratch_env, monkeypatch):
    INFER_FAULTS[fault](monkeypatch)
    result = run("cora.infer", scratch_env)
    assert not result["correct"], result["checks"]


def test_control_reading_is_well_above_the_limit(scratch_env):
    """The three-pass control misses the limit by a wide margin, not by
    rounding luck."""
    result = run("cora.infer", scratch_env, hook=controls.install("infer"))
    c = result["checks"]["forward_rel_err"]
    assert c["value"] > 3 * c["limit"]


def test_dot3_is_three_bf16_passes():
    rng = np.random.default_rng(0)
    a = rng.standard_normal((64, 256)).astype(np.float32)
    b = rng.standard_normal((256, 32)).astype(np.float32)
    exact = a.astype(np.float64) @ b
    err = np.abs(np.asarray(controls.dot3(a, b)) - exact).max()
    one = np.abs(np.asarray(jax.numpy.dot(
        jax.numpy.asarray(a, jax.numpy.bfloat16),
        jax.numpy.asarray(b, jax.numpy.bfloat16),
        preferred_element_type=jax.numpy.float32)) - exact).max()
    assert 10 * err < one                 # far better than one pass
    assert err > 1e-6 * np.abs(exact).max()   # and short of float32
