#!/usr/bin/env python3
"""Smoke run of the main path on one TPU chip, checked end to end.

    python chip_smoke.py [--seed N]

Run from the root of a checkout, one process per chip.  Every input is
generated from ``--seed``.  The phases run in order and the script stops
at the first failure:

a. device      — JAX's first device must be a TPU; on the CPU (for example
                 under ``JAX_PLATFORMS=cpu``) the script exits non-zero.
b. front door  — ``examples/scenarios/comparison.json`` through
   and serve     ``repro.api``: its SEC4 ``expect`` pins hold and its
                 conformance block compiles the kernel analogues for the
                 chip with every record ok.  Then a ``ServeEngine`` takes
                 requests from client threads that mix closed-form
                 scenarios with ``power_law`` trace scenarios at 10^7 edges
                 over 10^6 nodes at several tile capacities: no request
                 fails, no window falls back, and every served result is
                 bit-identical to serial ``evaluate_scenarios`` on cold
                 caches.
c. schedules   — on the same 10^7-edge trace, ``GraphTrace.schedules(caps,
                 engine="jax")`` over 16 power-of-two capacities gives
                 counts bit-identical to the numpy engine and to what the
                 server computed.
d. GCN-Cora    — layer 1 (1433->16) and layer 2 (16->7) of
                 ``configs/gcn_cora`` on a Cora-sized graph (2708 nodes,
                 10556 edges plus self-loops, symmetric norm, padded to
                 2816) through the fused kernel and the unfused pair,
                 against the full-precision dense and edge-list oracles.

Each phase prints one ``# smoke timing`` line (what ran, its sizes,
``device_kind``, compile and wall seconds).  These are smoke timings of a
cold process, not metrics.  The last line of standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.

The persistent compile cache goes where ``JAX_COMPILATION_CACHE_DIR``
says, else to ``<checkout>/.jax_cache``.  The on-disk trace store is off
unless ``REPRO_TRACE_CACHE`` is set, so every run computes its schedules.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

ROOT = Path(__file__).resolve().parent

#: The front door's numerics bar (the same as tests/test_conformance.py).
NUMERICS_REL_TOL = 1e-5
#: Trace the server, the serial oracle and the device engines share.
BIG_TRACE = {"n_nodes": 1_000_000.0, "n_edges": 10_000_000.0, "alpha": 1.6}
#: Capacities the server answers; a subset of the 16-capacity sweep.
SERVED_CAPS = (1024, 4096, 16384, 65536)
SWEEP_CAPS = tuple(2 ** k for k in range(4, 20))
CORA_NODES, CORA_EDGES, CORA_PADDED = 2708, 10556, 2816


class SmokeFailure(AssertionError):
    """A phase's check did not hold."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


class CompileClock:
    """Seconds the backend spent compiling, from JAX's monitoring events
    (every thread, including the serve dispatcher)."""

    def __init__(self) -> None:
        import jax

        self._lock = threading.Lock()
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.total += float(duration)


def report(phase: str, ran: str, sizes: dict, kind: str,
           compile_s: float, wall_s: float) -> None:
    print("# smoke timing (cold process, not a metric): " + json.dumps(
        {"phase": phase, "ran": ran, "sizes": sizes, "device_kind": kind,
         "compile_s": round(compile_s, 3), "wall_s": round(wall_s, 3)}),
        flush=True)


def run_phase(clock: CompileClock, kind: str, phase: str, ran: str,
              fn) -> object:
    c0, t0 = clock.total, time.perf_counter()
    sizes, result = fn()
    report(phase, ran, sizes, kind, clock.total - c0,
           time.perf_counter() - t0)
    return result


# -- b. front door and serve -----------------------------------------------
def _result_key(r) -> dict:
    d = r.to_dict()
    d.pop("serve", None)
    return d


def _trace_scenarios(seed: int):
    from repro.api import Scenario

    params = {**BIG_TRACE, "seed": float(seed)}
    return [[Scenario.trace(flow, dataset="power_law", params=params,
                            N=1433.0, T=7.0, widths=(1433.0, 16.0, 7.0),
                            tile_vertices=float(cap),
                            label=f"{flow}@power_law-1e7/cap{cap}")
             for flow in ("engn", "hygcn", "awb_gcn")]
            for cap in SERVED_CAPS]


def phase_front_door_and_serve(seed: int):
    from repro.api import ServeEngine, evaluate_scenarios, load_scenarios
    from repro.core.trace import clear_trace_cache, resolve_trace_dataset
    from repro.kernels import resolve_interpret

    check(resolve_interpret() is False,
          "kernels would run in interpret mode on this backend")
    batch = load_scenarios(ROOT / "examples" / "scenarios" / "comparison.json")
    res = evaluate_scenarios(batch)
    fails = res.expect_failures()
    check(not fails, f"comparison.json golden drift: {fails}")
    conf = [r.conformance for r in res.results if r.conformance is not None]
    check(conf and all(c.get("checked") and c["ok"] and c["records"] > 0
                       for c in conf),
          f"conformance on the chip-compiled analogues failed: {conf}")

    requests = [[s] for s in batch] + _trace_scenarios(seed)
    requests.append(list(requests[-1]))          # one duplicate request
    clear_trace_cache()
    with ServeEngine() as engine, ThreadPoolExecutor(8) as clients:
        futures = list(clients.map(engine.submit_future, requests))
        errors = [f.exception() for f in futures]
    check(all(e is None for e in errors), f"served requests failed: {errors}")
    metrics = engine.metrics()
    check(metrics["failed_requests"] == 0 and metrics["fallback_windows"] == 0,
          f"serve isolation hid a failure: {metrics}")
    served = [r for f in futures for r in f.result().results]

    # What the server computed, read back before the caches are dropped.
    trace_params = {**BIG_TRACE, "seed": float(seed)}
    trace = resolve_trace_dataset("power_law", trace_params)
    served_counts = {s.capacity: s.counts_dict()
                     for s in trace.schedules(SERVED_CAPS)}
    clear_trace_cache()
    flat = [s for req in requests for s in req]
    serial = evaluate_scenarios(flat).results
    check(len(served) == len(serial), "served result count differs")
    for a, b in zip(served, serial):
        check(_result_key(a) == _result_key(b),
              f"served result differs from serial: {a.scenario.label}")
    sizes = {"comparison_scenarios": len(batch), "requests": len(requests),
             "served_scenarios": len(served), "windows": metrics["windows"],
             "coalesce_rate": round(metrics["coalesce_rate"], 4),
             "trace_edges": int(trace.n_edges),
             "trace_nodes": int(trace.n_nodes),
             "served_caps": list(SERVED_CAPS)}
    return sizes, served_counts


# -- c. device schedule engine ---------------------------------------------
def _same_counts(a: dict, b: dict) -> bool:
    import numpy as np

    return all(np.array_equal(a[k], b[k]) if hasattr(a[k], "shape")
               else a[k] == b[k] for k in a)


def phase_schedules(seed: int, served_counts: dict):
    from repro.core.trace import resolve_trace_dataset

    trace = resolve_trace_dataset("power_law", {**BIG_TRACE,
                                                "seed": float(seed)})
    trace.clear_schedules()
    device = {s.capacity: s.counts_dict()
              for s in trace.schedules(SWEEP_CAPS, engine="jax")}
    trace.clear_schedules()
    host = {s.capacity: s.counts_dict()
            for s in trace.schedules(SWEEP_CAPS, engine="numpy")}
    for cap in SWEEP_CAPS:
        check(_same_counts(device[cap], host[cap]),
              f"engine=jax differs from numpy at capacity {cap}")
    for cap, counts in served_counts.items():
        check(_same_counts(device[cap], counts),
              f"engine=jax differs from the served schedule at cap {cap}")
    sizes = {"trace_edges": int(trace.n_edges), "sweep_caps": len(SWEEP_CAPS),
             "max_tiles": int(-(-trace.n_nodes // SWEEP_CAPS[0]))}
    return sizes, None


# -- d. GCN-Cora kernels at full width --------------------------------------
def _cora_graph(seed: int):
    import numpy as np

    from repro.data import synthetic

    g = synthetic.power_law_graph(seed, n_nodes=CORA_NODES,
                                  n_edges=CORA_EDGES, d_feat=1,
                                  self_loops=True)
    snd = g.senders.astype(np.int64)
    rcv = g.receivers.astype(np.int64)
    deg_in = np.bincount(rcv, minlength=CORA_NODES).astype(np.float64)
    deg_out = np.bincount(snd, minlength=CORA_NODES).astype(np.float64)
    coeff = (1.0 / np.sqrt(deg_in[rcv] * deg_out[snd])).astype(np.float32)
    adj = np.zeros((CORA_PADDED, CORA_PADDED), np.float32)
    np.add.at(adj, (rcv, snd), coeff)
    return snd, rcv, coeff, adj


def phase_gcn_cora(seed: int):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import gcn_cora
    from repro.kernels import ops, ref

    cfg = gcn_cora.make_config()
    snd, rcv, coeff, adj = _cora_graph(seed)
    rng = np.random.default_rng(seed)
    x = np.zeros((CORA_PADDED, cfg.d_in), np.float32)
    x[:CORA_NODES] = rng.standard_normal((CORA_NODES, cfg.d_in))
    widths = [(cfg.d_in, cfg.d_hidden), (cfg.d_hidden, cfg.n_classes)]
    ws = [jnp.asarray(rng.standard_normal(wd) / np.sqrt(wd[0]), jnp.float32)
          for wd in widths]
    a = jnp.asarray(adj)
    h = jnp.asarray(x)
    snd_d, rcv_d, coeff_d = map(jnp.asarray, (snd, rcv, coeff))
    hi = jax.lax.Precision.HIGHEST
    errors = {}
    for layer, w in enumerate(ws, start=1):
        fused = ops.gnn_aggregate_combine.lower(a, h, w).compile()(a, h, w)
        agg = ops.gnn_aggregate.lower(a, h).compile()(a, h)
        unfused = ops.gnn_combine.lower(agg, w).compile()(agg, w)
        dense = ref.fused_aggregate_combine_ref(a, h, w)
        edges = jnp.dot(ref.edge_list_aggregate_ref(h, snd_d, rcv_d, coeff_d,
                                                    CORA_PADDED), w,
                        precision=hi)
        fused, unfused, dense, edges = jax.block_until_ready(
            (fused, unfused, dense, edges))
        oracles = {"dense": dense, "edge_list": edges}
        for name, got in (("fused", fused), ("unfused", unfused)):
            check(got.shape == (CORA_PADDED, w.shape[1]),
                  f"layer {layer} {name}: shape {got.shape}")
            for oracle_name, oracle in oracles.items():
                denom = float(jnp.max(jnp.abs(oracle))) + 1e-9
                err = float(jnp.max(jnp.abs(got - oracle))) / denom
                check(np.isfinite(err), f"layer {layer} {name}: not finite")
                errors[f"layer{layer}_{name}_vs_{oracle_name}"] = err
        h = jax.nn.relu(fused)
    worst = max(errors.values())
    check(worst < NUMERICS_REL_TOL,
          f"GCN-Cora kernels miss the {NUMERICS_REL_TOL:g} numerics bar: "
          f"{errors}")
    sizes = {"nodes": CORA_NODES, "edges": CORA_EDGES + CORA_NODES,
             "padded_nodes": CORA_PADDED, "widths": widths,
             "max_rel_err": worst, "rel_err": errors}
    return sizes, None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    os.environ.setdefault("REPRO_TRACE_CACHE", "0")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(ROOT / "src"))
    try:
        from repro.compile_cache import enable_compile_cache
    except ImportError as exc:
        print(f"chip_smoke: the repro package is not under {ROOT / 'src'} "
              f"({exc}); run this script from a checkout", file=sys.stderr)
        return 2

    enable_compile_cache()
    import jax

    # a. device
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX's first device is "
              f"{dev.platform!r}); this script runs only on a TPU",
              file=sys.stderr)
        return 1
    kind, count = dev.device_kind, len(jax.devices())
    report("device", "jax.devices()", {"count": count}, kind, 0.0, 0.0)
    clock = CompileClock()

    served_counts = run_phase(
        clock, kind, "front_door_and_serve",
        "repro.api.evaluate_scenarios(comparison.json) + ServeEngine "
        "vs serial evaluate_scenarios",
        lambda: phase_front_door_and_serve(args.seed))
    run_phase(clock, kind, "schedules",
              "GraphTrace.schedules(engine='jax') vs numpy",
              lambda: phase_schedules(args.seed, served_counts))
    run_phase(clock, kind, "gcn_cora",
              "ops.gnn_aggregate_combine and gnn_aggregate->gnn_combine "
              "vs ref oracles",
              lambda: phase_gcn_cora(args.seed))

    print(json.dumps({"ok": True, "device": {"platform": dev.platform,
                                             "kind": kind, "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
