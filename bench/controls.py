"""Controls: the reference put in the program's place, one step short of
what the configuration states. Each must come out not correct.

* ``infer``: the reference forward in float32 with every product taken in
  three bf16 passes (``Precision.HIGH``), where the configuration states
  float32 at ``highest``. The passes are written out, so the control
  reads the same on the CPU as on the chip.
* ``sweep``: the reference counts with the halo taken as the remote edges,
  without merging repeated sources: the configuration's guarantee is the
  exact unique-source halo, and dropping the merge is the cheaper step.

:func:`install` swaps the control into a driver after its set-up.
"""

from __future__ import annotations

from types import SimpleNamespace

import numpy as np

import graphs
import reference

__all__ = ["dot3", "install"]


def dot3(a, b):
    """``a @ b`` for float32 operands in three bf16 passes, float32 sums:
    ``hi*hi + hi*lo + lo*hi`` with ``x = hi + lo`` split in bf16.

    The split uses ``reduce_precision``, which XLA keeps; a round trip
    through a bf16 cast may be folded away as excess precision."""
    import jax
    import jax.numpy as jnp

    def bf16(x):
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)

    def split(x):
        hi = bf16(x)
        return hi.astype(jnp.bfloat16), bf16(x - hi).astype(jnp.bfloat16)

    (a_hi, a_lo), (b_hi, b_lo) = split(a), split(b)

    def mm(x, y):
        return jnp.dot(x, y, preferred_element_type=jnp.float32)

    return mm(a_hi, b_hi) + (mm(a_hi, b_lo) + mm(a_lo, b_hi))


def _infer_control(a, x, w1, w2):
    import jax

    h = jax.nn.relu(dot3(dot3(a, x), w1))
    return dot3(dot3(a, h), w2)


def _sweep_control(driver):
    snd, rcv = graphs.graph_arrays(driver.cfg, driver.data_dir)
    n = driver.cfg["n_nodes"]
    memo = {}

    def compute(caps):
        out = []
        for cap in caps:
            if cap not in memo:
                ref = reference.tile_counts(snd, rcv, n, cap)
                ref["halo_counts"] = ref["remote_edge_counts"]
                memo[cap] = SimpleNamespace(**{
                    k: (v.astype(np.float64) if isinstance(v, np.ndarray)
                        else v) for k, v in ref.items()})
            out.append(memo[cap])
        return tuple(out)

    return compute


def install(kind: str):
    """A ``driver_hook`` that puts the control for ``kind`` in place."""
    def hook(driver):
        if kind == "infer":
            import jax

            driver.forward = jax.jit(_infer_control)
        elif kind == "sweep":
            driver.compute = _sweep_control(driver)
        else:
            raise ValueError(f"no control for driver kind {kind!r}")
    return hook
