"""Main-path kernels compiled for a described TPU v5e (no chip attached).

The TPU compiler is installed with jax and compiles for a topology that is
described, not attached: what it refuses here it would refuse on the chip.
Every case passes ``interpret=False`` explicitly, at the widths the system
runs: GCN-Cora (``configs/gcn_cora``) on a Cora-sized graph padded to 2816
nodes, and the device schedule engine at 10^7 unique pairs and at
ogbn-products' 123.7 M.  Nothing runs; results are checked on the chip by
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
from repro.kernels import gated_edge as ge
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


def test_gated_edge_kernel_compiles_at_pattern_size(one_chip):
    """GatedGCN/PATTERN's edge layer at width 70 (72 sublanes): 1,691
    blocks of 512 edge slots over 154 node tiles, traced under
    ``default_matmul_precision("highest")`` as the model runs it, which
    the kernel's bf16 one-hot products must not take up."""
    dp, be, node_slots, blocks = 72, 512, 154 * 128, 1691
    with jax.default_matmul_precision("highest"):
        compiled = _compile(
            functools.partial(ge.gated_edge_layer, interpret=False),
            _spec(one_chip, (blocks, dp, be)),
            _spec(one_chip, (3 * dp, node_slots), jnp.bfloat16),
            _spec(one_chip, (6 * dp, node_slots), jnp.bfloat16),
            _spec(one_chip, (dp, 6 * dp), jnp.bfloat16),
            _spec(one_chip, (dp, 1)),
            _spec(one_chip, (dp, 1)),
            _spec(one_chip, (blocks, 2, be), jnp.int32),
            _spec(one_chip, (blocks,), jnp.int32),
            _spec(one_chip, (blocks,), jnp.int32))
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo and "%gated_edge_layer" in hlo
    e_out, sums = compiled.out_info
    assert e_out.shape == (blocks, dp, be)
    assert sums.shape == (2 * dp, node_slots)


def _compile_schedule_engine(sharding, u, n_tiles_pad):
    compiled = sr._schedule_counts_jnp.lower(
        _spec(sharding, (u,), jnp.int32), _spec(sharding, (u,), jnp.int32),
        _spec(sharding, (u,), jnp.bool_), _spec(sharding, (u,), jnp.int32),
        _spec(sharding, (), jnp.int32), n_tiles_pad,
        interpret=False).compile()
    halo, cut = compiled.out_info
    assert halo.shape == cut.shape == (n_tiles_pad,)
    assert halo.dtype == cut.dtype == jnp.int32
    return compiled.as_text()


def test_device_schedule_engine_compiles_at_1e7_pairs(one_chip):
    n_tiles_pad = 62_500   # a 10^6-node trace at capacity 16
    hlo = _compile_schedule_engine(one_chip, 10_000_000, n_tiles_pad)
    # The MXU histogram kernel at or below the crossover, scatters above.
    assert ("tpu_custom_call" in hlo) == (
        n_tiles_pad <= sr.MXU_HIST_MAX_TILES)


def test_products_sweep_takes_the_mxu_histogram(one_chip):
    """ogbn-products' capacity sweep: U = 123,718,280 unique pairs and
    2,392 tiles at capacity 2^10 compile to the MXU histogram kernel."""
    hlo = _compile_schedule_engine(one_chip, 123_718_280, 2392)
    assert "tpu_custom_call" in hlo


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
