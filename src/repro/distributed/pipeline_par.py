"""GPipe-style pipeline parallelism over a dedicated ``pipe`` mesh axis.

For trillion-parameter configs (arctic-480b at fp32 optimizer states) a
third parallelism dimension becomes necessary; this module provides the
schedule as a composable primitive: stages hold contiguous layer groups,
microbatches stream through ``ppermute`` hops, outputs collect on the last
stage and broadcast.  The schedule below is plain GPipe (fill + drain
bubble of (S-1)/(M+S-1)); 1F1B re-ordering is an orthogonal optimization
recorded as future work in DESIGN.md.

Differentiable end-to-end: ppermute/fori_loop transpose cleanly, so
``jax.grad`` through :func:`gpipe_apply` yields pipeline-parallel BPTT.
"""

from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

Array = jax.Array

__all__ = ["gpipe_apply"]


def gpipe_apply(stage_fn: Callable, stage_params, x_micro: Array, *,
                mesh: Mesh, axis: str = "pipe") -> Array:
    """Run ``stage_fn`` S times (once per stage) over M microbatches.

    stage_params: pytree with leading dim S (sharded over ``axis``).
    x_micro: (M, micro_batch, ...) replicated input.
    Returns (M, micro_batch, ...) — final-stage outputs, replicated.
    """
    n_stages = mesh.shape[axis]

    def local(params_loc, xs):
        params_loc = jax.tree_util.tree_map(lambda a: a[0], params_loc)
        r = jax.lax.axis_index(axis)
        m = xs.shape[0]
        total = m + n_stages - 1
        fwd_perm = [(i, i + 1) for i in range(n_stages - 1)]

        def step(t, carry):
            buf_in, outs = carry
            mb_idx = jnp.clip(t, 0, m - 1)
            x_in = jnp.where(r == 0, xs[mb_idx], buf_in)
            active = (t - r >= 0) & (t - r < m)
            y = stage_fn(params_loc, x_in)
            y = jnp.where(active, y, jnp.zeros_like(y))
            out_idx = jnp.clip(t - (n_stages - 1), 0, m - 1)
            store = (r == n_stages - 1) & (t >= n_stages - 1)
            outs = jnp.where(store, outs.at[out_idx].set(y), outs)
            buf_next = jax.lax.ppermute(y, axis, fwd_perm)
            return buf_next, outs

        buf0 = jnp.zeros_like(xs[0])
        outs0 = jnp.zeros_like(xs)
        _, outs = jax.lax.fori_loop(0, total, step, (buf0, outs0))
        # Broadcast final-stage outputs to every rank (replicated out-spec).
        outs = jax.lax.psum(
            jnp.where(r == n_stages - 1, outs, jnp.zeros_like(outs)), axis)
        return outs

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
        check_vma=False,
    )(stage_params, x_micro)
