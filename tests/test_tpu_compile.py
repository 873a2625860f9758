"""Main-path kernels compiled for a described TPU v5e (no chip attached).

The TPU compiler is installed with jax and compiles for a topology that is
described, not attached: what it refuses here it would refuse on the chip.
Every case passes ``interpret=False`` explicitly, at the widths the system
runs: GCN-Cora (``configs/gcn_cora``) on a Cora-sized graph padded to 2816
nodes, the device schedule engine at 10^7 unique pairs, and the Pallas
histogram.  Nothing runs; results are checked on the chip by
``chip_smoke.py``.

The topology is described inside a module-scoped fixture, never at import:
only one process may hold the TPU library, and under pytest-xdist every
worker imports this file.  The fixture skips where no v5e can be
described and keeps the persistent compile cache off around the compiles
(an entry compiled for a described chip cannot be read back without one).
"""

import functools
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import gcn_cora
from repro.core import registry
from repro.core.conformance import (OperatingPoint, ProgramMeasurement,
                                    conformance_records, measure_program)
from repro.kernels import edge_aggregate as ea
from repro.kernels import edge_aggregate_unfused as eu
from repro.kernels import segment_reduce as sr

#: Cora (2708 nodes) padded to the kernels' 256-node blocks.
CORA_PADDED_NODES = 2816


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as exc:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {exc}")
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_enabled)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile()


def _cora_widths():
    cfg = gcn_cora.make_config()
    return [(cfg.d_in, cfg.d_hidden), (cfg.d_hidden, cfg.n_classes)]


@pytest.mark.parametrize("layer,dtype", [(0, jnp.float32), (1, jnp.float32),
                                         (0, jnp.bfloat16)],
                         ids=["layer1", "layer2", "layer1-bf16"])
def test_fused_kernel_compiles_at_gcn_cora_width(one_chip, layer, dtype):
    # bf16 guards mxu_dot's precision choice: Mosaic refuses a full-f32
    # contraction of a bf16 operand.
    f, t = _cora_widths()[layer]
    n = CORA_PADDED_NODES
    compiled = _compile(
        functools.partial(ea.fused_aggregate_combine, interpret=False),
        _spec(one_chip, (n, n), dtype), _spec(one_chip, (n, f), dtype),
        _spec(one_chip, (f, t), dtype))
    assert "tpu_custom_call" in compiled.as_text()


def test_unfused_pair_compiles_at_gcn_cora_layer1(one_chip):
    f, t = _cora_widths()[0]
    n = CORA_PADDED_NODES
    agg = _compile(functools.partial(eu.aggregate_pass, interpret=False),
                   _spec(one_chip, (n, n)), _spec(one_chip, (n, f)))
    comb = _compile(functools.partial(eu.combine_pass, interpret=False),
                    _spec(one_chip, (n, f)), _spec(one_chip, (f, t)))
    for compiled in (agg, comb):
        assert "tpu_custom_call" in compiled.as_text()


def test_device_schedule_engine_compiles_at_1e7_pairs(one_chip):
    u = 10_000_000
    n_tiles_pad = 62_500   # a 10^6-node trace at capacity 16
    compiled = sr._schedule_counts_jnp.lower(
        _spec(one_chip, (u,), jnp.int32), _spec(one_chip, (u,), jnp.int32),
        _spec(one_chip, (u,), jnp.bool_), _spec(one_chip, (u,), jnp.int32),
        _spec(one_chip, (), jnp.int32), n_tiles_pad).compile()
    halo, cut = compiled.out_info
    assert halo.shape == cut.shape == (n_tiles_pad,)
    assert halo.dtype == cut.dtype == jnp.int32


@pytest.mark.parametrize("n_pad", [128, 1024])
def test_histogram_kernel_compiles(one_chip, n_pad):
    block = sr.DEFAULT_BLOCK_EDGES
    e_pad = 16 * block
    compiled = _compile(
        functools.partial(sr.padded_histogram, n_pad=n_pad,
                          block_edges=block, interpret=False),
        _spec(one_chip, (1, e_pad), jnp.int32),
        _spec(one_chip, (1, e_pad)))
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.out_info.shape == (1, n_pad)


def test_fused_conformance_boundary_on_chip_program(one_chip):
    """The §10 boundary check on the chip-compiled fused program at the
    front door's default conformance point (K=256, N=16, T=8, 128x128)."""
    point = OperatingPoint(256, 16, 8, 128, 128)
    K, N, T = point.K, point.N, point.T
    compiled = _compile(
        functools.partial(ea.fused_aggregate_combine, block_n=point.Bn,
                          block_k=point.Bk, interpret=False),
        _spec(one_chip, (K, K)), _spec(one_chip, (K, N)),
        _spec(one_chip, (N, T)))
    assert "tpu_custom_call" in compiled.as_text()
    acct = ea.fused_block_streams(K, N, T, block_n=point.Bn,
                                  block_k=point.Bk,
                                  elem_bytes=point.elem_bytes)
    meas = measure_program(ProgramMeasurement("fused", compiled,
                                              acct["grid"], acct["streams"]))
    floor = meas["distinct_total_bytes"]
    assert meas["boundary"]["total_bytes"] == floor
    assert meas["xla_bytes_accessed"] >= floor
    records = conformance_records(registry.get("spmm_tiled"), point,
                                  measures=[meas])
    assert records and all(r.ok for r in records), [
        str(r) for r in records if not r.ok]
    assert np.isfinite([r.measured_bytes for r in records]).all()
