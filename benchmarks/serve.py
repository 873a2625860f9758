"""Throughput-under-load benchmark for the §18 scenario-serving engine.

The first benchmark in the repo that measures *service* behaviour rather
than single-shot wall time: a synthetic many-client load of mixed
tile / full-graph / trace / hetero / minibatch / tune requests, sampled
with heavy duplication from a small scenario pool, is driven through

* the **naive per-request loop** — one ``evaluate_scenarios`` call per
  request, exactly what N independent CLI invocations would cost with
  warm in-process caches; and
* the **serve engine** — every request submitted concurrently from
  client threads into :class:`repro.api.serve.ServeEngine`, which
  coalesces identical scenarios across requests inside micro-batching
  windows and shares one broadcast evaluation per plan group.

Both paths run against warm caches, so the measured gap is pure
cross-request coalescing + planner amortization, not cold-start noise.

Gates (exit 1 on failure, ``# GATE FAILURE`` lines on stderr):

* **drift** — every served result must be bit-identical to the serial
  oracle (total/offchip/cache/onchip bits, iterations, every breakdown
  term).  The serve engine evaluates through the same planner, so any
  drift is a scatter bug.
* **coalesce** — a duplicate-heavy load must show a coalesce rate > 0
  (N duplicate requests -> fewer evaluations than scenarios).
* **speedup** (full mode only) — served scenarios/sec must be >= 10x
  the naive loop's.

``--smoke`` keeps the request count CI-sized; the committed
``BENCH_serve.json`` comes from a full run.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

# Hermetic by default: the disk cache participates through a throwaway
# root (shared-warm-store counters show up in the report) unless the
# caller pinned one.  Must happen before repro imports read the env.
_TMP_CACHE = None
if "REPRO_TRACE_CACHE" not in os.environ:
    _TMP_CACHE = tempfile.TemporaryDirectory(prefix="repro-serve-bench-")
    os.environ["REPRO_TRACE_CACHE"] = _TMP_CACHE.name
    os.environ.setdefault("REPRO_TRACE_CACHE_MIN_EDGES", "0")

import numpy as np

from repro.api import Scenario, ServeEngine, evaluate_scenarios
from repro.core import registry, schedule_cache
from repro.core.trace import reset_trace_stats, trace_cache_info

TRACE_PARAMS = {"n_nodes": 4000.0, "n_edges": 16000.0, "seed": 1.0}
TYPED_PARAMS = {"n_nodes": 2000.0, "n_edges": 12000.0, "seed": 0.0}


def build_pool() -> list[Scenario]:
    """~24 distinct scenarios across every kind the front door serves."""
    dataflows = list(registry.names())
    pool: list[Scenario] = []
    for df in dataflows:
        for K in (256.0, 1024.0, 4096.0):
            pool.append(Scenario.tile(
                df, K=K, label=f"tile-{df}-{int(K)}", workload="serve-load"))
    for df in dataflows[:2]:
        pool.append(Scenario.full_graph(
            df, V=2708.0, E=10556.0, N=1433.0, T=7.0,
            widths=(1433.0, 16.0, 7.0), tile_vertices=512.0,
            label=f"full-{df}", workload="serve-load"))
    for df in dataflows[:2]:
        for cap in (256.0, 1024.0):
            pool.append(Scenario.trace(
                df, dataset="power_law", params=TRACE_PARAMS,
                N=64.0, T=16.0, tile_vertices=cap,
                widths=(64.0, 32.0, 16.0),
                label=f"trace-{df}-{int(cap)}", workload="serve-load"))
    pool.append(Scenario.hetero(
        dataflows[0], dataset="typed_power_law", n_relations=3,
        params=TYPED_PARAMS, N=[30.0, 20.0, 10.0], T=5.0,
        tile_vertices=512.0, label="hetero-serve", workload="serve-load"))
    pool.append(Scenario.minibatch(
        dataflows[1], dataset="power_law", params=TRACE_PARAMS,
        batch_nodes=64, fanout=(4, 4), n_batches=4, N=64.0, T=16.0,
        label="minibatch-serve", workload="serve-load"))
    pool.append(Scenario.trace(
        dataflows[0], dataset="power_law", params=TRACE_PARAMS,
        N=32.0, T=8.0, tile_vertices=512.0,
        optimize={"objective": "movement",
                  "space": {"tile_vertices": [256.0, 512.0, 1024.0]}},
        label="tune-serve", workload="serve-load"))
    return pool


def build_requests(pool, n_requests: int, seed: int) -> list[list[Scenario]]:
    """Duplicate-heavy load: each request samples 1-3 pool scenarios."""
    rng = np.random.default_rng(seed)
    sizes = rng.integers(1, 4, size=n_requests)
    return [[pool[i] for i in rng.integers(0, len(pool), size=int(k))]
            for k in sizes]


def _result_record(r) -> dict:
    return {
        "total_bits": r.total_bits,
        "total_iterations": r.total_iterations,
        "offchip_bits": r.offchip_bits,
        "cache_bits": r.cache_bits,
        "onchip_bits": r.onchip_bits,
        "breakdown": dict(r.breakdown),
        "iteration_breakdown": dict(r.iteration_breakdown),
        "n_tiles": r.n_tiles,
    }


def drift_gate(serial, served) -> list[str]:
    """Bit-exact comparison of every per-request result pair."""
    drift = []
    for i, (a_req, b_req) in enumerate(zip(serial, served)):
        if len(a_req) != len(b_req):
            drift.append(f"request {i}: {len(a_req)} serial results vs "
                         f"{len(b_req)} served")
            continue
        for j, (a, b) in enumerate(zip(a_req, b_req)):
            ra, rb = _result_record(a), _result_record(b)
            if ra != rb:
                keys = [k for k in ra if ra[k] != rb[k]]
                drift.append(f"request {i} scenario {j} "
                             f"({a.scenario.label}): fields {keys} differ "
                             f"(e.g. {keys[0]}: {ra[keys[0]]!r} vs "
                             f"{rb[keys[0]]!r})")
            if len(drift) > 20:
                return drift
    return drift


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m benchmarks.serve",
        description="Serve-engine throughput benchmark: coalesced "
                    "concurrent requests vs the naive per-request loop.")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized load (fewer requests, no speedup gate)")
    ap.add_argument("--requests", type=int, default=None,
                    help="request count (default 1500; smoke 300)")
    ap.add_argument("--clients", type=int, default=16,
                    help="concurrent submitter threads (default 16)")
    ap.add_argument("--window", type=float, default=0.002,
                    help="serve micro-batching window seconds "
                         "(default 0.002)")
    ap.add_argument("--pool-size", type=int, default=None,
                    help="truncate the scenario pool (smaller pool -> "
                         "higher duplicate ratio)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write the benchmark report JSON")
    args = ap.parse_args(argv)

    n_requests = args.requests or (300 if args.smoke else 1500)
    pool = build_pool()
    if args.pool_size is not None:
        pool = pool[:max(1, args.pool_size)]
    requests = build_requests(pool, n_requests, args.seed)
    n_scen = sum(len(r) for r in requests)
    distinct_used = len({s for req in requests for s in req})
    dup_ratio = 1.0 - distinct_used / n_scen

    print(f"# load: {n_requests} requests / {n_scen} scenarios, "
          f"{distinct_used} distinct (duplicate ratio {dup_ratio:.3f}), "
          f"pool {len(pool)}")

    # Warm both paths identically: resolve every trace, compute every
    # schedule, run the tuner once.  From here on the gap is coalescing.
    evaluate_scenarios(pool)
    reset_trace_stats()
    schedule_cache.reset_cache_stats()

    # -- naive per-request loop -------------------------------------------
    t0 = time.perf_counter()
    serial = [evaluate_scenarios(req).results for req in requests]
    naive_s = time.perf_counter() - t0
    naive_rate = n_scen / naive_s
    print(f"# naive loop: {naive_s:.3f}s ({naive_rate:,.0f} scenarios/sec)")

    # -- served, coalesced ------------------------------------------------
    from concurrent.futures import ThreadPoolExecutor

    reset_trace_stats()
    stats0 = trace_cache_info()["stats"]
    engine = ServeEngine(window_s=args.window)
    n_clients = max(1, args.clients)
    # Each client owns an interleaved slice of the request stream and
    # fires it as fast as the engine accepts — the closed-loop burst a
    # fleet of independent callers produces.
    chunks = [requests[c::n_clients] for c in range(n_clients)]
    t0 = time.perf_counter()
    with engine:
        with ThreadPoolExecutor(max_workers=n_clients) as pool_ex:
            chunk_handles = list(pool_ex.map(
                lambda reqs: [engine.submit_future(r) for r in reqs],
                chunks))
        handles = [None] * len(requests)
        for c, hs in enumerate(chunk_handles):
            for k, h in enumerate(hs):
                handles[c + k * n_clients] = h
        served_results = [h.result() for h in handles]
    served_s = time.perf_counter() - t0
    stats1 = trace_cache_info()["stats"]
    served = [sr.results for sr in served_results]
    served_rate = n_scen / served_s
    latencies_ms = np.array([sr.serve["latency_s"] * 1e3
                             for sr in served_results])
    metrics = engine.metrics()
    speedup = served_rate / naive_rate
    print(f"# served: {served_s:.3f}s ({served_rate:,.0f} scenarios/sec), "
          f"{metrics['windows']} windows, "
          f"{metrics['evaluations']} evaluations, "
          f"coalesce rate {metrics['coalesce_rate']:.3f}")
    print(f"# latency p50 {np.percentile(latencies_ms, 50):.1f}ms "
          f"p99 {np.percentile(latencies_ms, 99):.1f}ms; "
          f"speedup {speedup:.1f}x")

    # -- gates ------------------------------------------------------------
    drift = drift_gate(serial, served)
    gates = {
        "drift_ok": not drift,
        "coalesce_ok": metrics["coalesce_rate"] > 0.0,
        "speedup_ok": bool(args.smoke or speedup >= 10.0),
    }
    for line in drift:
        print(f"# GATE FAILURE drift: {line}", file=sys.stderr)
    if not gates["coalesce_ok"]:
        print(f"# GATE FAILURE coalesce: rate "
              f"{metrics['coalesce_rate']} under duplicate ratio "
              f"{dup_ratio:.3f}", file=sys.stderr)
    if not gates["speedup_ok"]:
        print(f"# GATE FAILURE speedup: {speedup:.2f}x < 10x",
              file=sys.stderr)

    report = {
        "config": {
            "smoke": args.smoke, "requests": n_requests,
            "clients": args.clients, "window_s": args.window,
            "pool": len(pool), "seed": args.seed,
        },
        "load": {
            "scenarios": n_scen,
            "distinct_scenarios": distinct_used,
            "duplicate_ratio": dup_ratio,
            "kinds": sorted({("tune" if s.optimize is not None
                              else s.graph_kind) for s in pool}),
        },
        "naive": {"seconds": naive_s, "scenarios_per_sec": naive_rate},
        "served": {
            "seconds": served_s,
            "scenarios_per_sec": served_rate,
            "latency_ms_p50": float(np.percentile(latencies_ms, 50)),
            "latency_ms_p99": float(np.percentile(latencies_ms, 99)),
            "windows": metrics["windows"],
            "evaluations": metrics["evaluations"],
            "coalesce_rate": metrics["coalesce_rate"],
            "fallback_windows": metrics["fallback_windows"],
            "trace_stats": {k: stats1[k] - stats0[k] for k in stats1},
        },
        "speedup": speedup,
        "disk_cache": schedule_cache.cache_stats(),
        "gates": gates,
        "drift": drift,
    }
    if args.json:
        with open(args.json, "w") as f:
            json.dump(report, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"# wrote {args.json}")
    return 0 if all(gates.values()) else 1


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    sys.exit(main())
