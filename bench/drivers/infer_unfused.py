"""Full-graph GCN inference through the unfused kernel pair, in a closed loop.

The closed loop, inputs and check of ``infer.py`` (``cora.infer``), with
each layer run as ``repro.kernels.ops.gnn_aggregate`` and then
``ops.gnn_combine``: the aggregate ``Â H`` is written to HBM by the first
kernel and read back by the second, HyGCN's inter-phase buffer, where the
fused kernel keeps it in VMEM. One forward is ``Â relu(Â X W1) W2``,
jitted as one program of four kernel calls.
"""

from __future__ import annotations

from pathlib import Path

import harness


def program_forward(a, x, w1, w2):
    """The system under test: two aggregate-then-combine layers."""
    import jax

    from repro.kernels import ops

    h = jax.nn.relu(ops.gnn_combine(ops.gnn_aggregate(a, x), w1))
    return ops.gnn_combine(ops.gnn_aggregate(a, h), w2)


# A private copy of the fused driver, with this forward in its place.
_infer = harness.load_module(Path(__file__).with_name("infer.py"),
                             "bench_driver_infer_for_unfused")
_infer.program_forward = program_forward
Driver = _infer.Driver
