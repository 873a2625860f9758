"""The control of ``pattern.infer``: a plain GatedGCN forward put in the
program's place, one step short of what the configuration states. Every
product is taken in three bf16 passes (``controls.dot3``, as
``Precision.HIGH``) where the configuration states float32 at
``highest``; gathers and sums stay exact float32. It must come out not
correct.

``cora.infer_unfused`` shares ``cora.infer``'s reference, limit and
control (``controls.install("infer")``).
"""

from __future__ import annotations

import controls

__all__ = ["install"]


def _control_forward(params, g, layout):
    import jax
    import jax.numpy as jnp

    dot3 = controls.dot3
    snd, rcv, n = g.senders, g.receivers, g.n_nodes
    seg = lambda x: jax.ops.segment_sum(x, rcv, num_segments=n)

    def bn(x, p):
        scale = p["gamma"] * jax.lax.rsqrt(p["var"] + 1e-5)
        return x * scale + (p["beta"] - p["mean"] * scale)

    h = params["embed_h"][g.node_feat]
    e = jnp.broadcast_to(params["embed_e"].sum(axis=0) + params["embed_e_b"],
                         (snd.shape[0], h.shape[1]))

    def body(carry, lp):
        h, e = carry
        lin = lambda name, x: dot3(x, lp[name]) + lp["b" + name]
        e_hat = lin("C", e) + lin("D", h)[rcv] + lin("E", h)[snd]
        sig = jax.nn.sigmoid(e_hat)
        msgs = seg(sig * lin("B", h)[snd]) / (seg(sig) + 1e-6)
        h_new = h + jax.nn.relu(bn(lin("A", h) + msgs, lp["bn_h"]))
        return (h_new, e + jax.nn.relu(bn(e_hat, lp["bn_e"]))), None

    (h, _), _ = jax.lax.scan(body, (h, e), params["layers"])
    for i, p in enumerate(params["mlp"]):
        h = dot3(h, p["w"]) + p["b"]
        h = jax.nn.relu(h) if i < len(params["mlp"]) - 1 else h
    return h


def install(kind: str):
    """A ``driver_hook`` that puts the control for ``kind`` in place."""
    if kind == "infer_unfused":
        return controls.install("infer")
    if kind != "infer_gated":
        raise ValueError(f"no control for driver kind {kind!r}")

    def hook(driver):
        import jax

        driver.forward = jax.jit(_control_forward)
    return hook
