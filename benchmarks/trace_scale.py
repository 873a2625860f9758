"""Trace-scheduling scale benchmark: ``PYTHONPATH=src python -m benchmarks.trace_scale``.

Times the DESIGN.md §13 amortized multi-capacity trace engine and the
DESIGN.md §14 **sharded streaming pipeline** against the PR-4
per-capacity reference (one ``np.unique`` sort per capacity) on
streaming power-law graphs from 10⁵ to 10⁸ edges, across a 16-point
power-of-two tile-capacity sweep — the sweep shape the paper's
comparative question actually asks for.

Every edge count runs the sharded pipeline (per-shard generation +
local sort → range-bucketed exchange → per-bucket factorization → O(U)
CSR → ``engine="sharded"`` capacity sweep) with per-stage wall times
and peak-RSS snapshots.  Up to ``--single-max-edges`` it *also* runs
the single-host path and enforces the distributed drift gate: the
sharded factorization must be **bit-identical** (values, order, dtypes)
to the single-host one, and every sharded schedule bit-identical to the
amortized engine and (up to ``--ref-max-edges``) to the PR-4 reference,
plus the structural invariants (vertex/edge count conservation,
``n_tiles = ceil(V / cap)``).  Exits non-zero on any drift — the CI
``trace-scale-smoke`` / ``trace-shard-smoke`` gates.

Outputs one row per edge count and with ``--json`` writes
``BENCH_trace_scale.json`` for PR-over-PR diffing.  ``--smoke`` runs a
≤30 s budget (small graphs, reference everywhere); the full run
schedules a 10⁸-edge graph end-to-end through the sharded path alone.
When the on-disk schedule cache is enabled (``REPRO_TRACE_CACHE``), the
benchmark also records cold-vs-warm ``resolve_trace_dataset`` times for
the largest single-host graph (warm resolves are mmap-lazy in cache
format v2, so the warm number is size-independent).

Peak-RSS note: ``ru_maxrss`` is a process-lifetime high-water mark, so
per-stage values are monotone "peak so far" snapshots — the first stage
that spikes shows where the ceiling came from.
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np


def _pow2_caps(n_nodes: int, points: int) -> list[int]:
    """Capacities n_nodes/2, n_nodes/4, ... — ``points`` distinct values."""
    caps: list[int] = []
    i = 1
    while len(caps) < points:
        cap = max(1, n_nodes >> i)
        if caps and cap == caps[-1]:
            break  # graph too small for more distinct points
        caps.append(cap)
        i += 1
    return caps


def _check_schedules(trace, caps, scheds, refs=None,
                     label: str = "per-capacity reference") -> list[str]:
    """Drift gate: structural invariants + bit-parity vs a reference."""
    errors = []
    for cap, sched in zip(caps, scheds):
        n_tiles = -(-trace.n_nodes // cap)
        if sched.n_tiles != n_tiles:
            errors.append(f"cap={cap}: n_tiles {sched.n_tiles} != {n_tiles}")
        if int(sched.vertex_counts.sum()) != trace.n_nodes:
            errors.append(f"cap={cap}: vertex counts sum "
                          f"{int(sched.vertex_counts.sum())} != V")
        if int(sched.edge_counts.sum()) != trace.n_edges:
            errors.append(f"cap={cap}: edge counts sum "
                          f"{int(sched.edge_counts.sum())} != E")
    if refs is not None:
        for cap, sched, ref in zip(caps, scheds, refs):
            for f in ("vertex_counts", "edge_counts", "halo_counts",
                      "remote_edge_counts"):
                if not np.array_equal(getattr(sched, f), getattr(ref, f)):
                    errors.append(f"cap={cap}: {f} drifted from the {label}")
    return errors


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--json", nargs="?", const="BENCH_trace_scale.json",
                    default=None, metavar="PATH",
                    help="also write a summary JSON "
                         "(default BENCH_trace_scale.json)")
    ap.add_argument("--smoke", action="store_true",
                    help="small-budget CI mode (~seconds, reference "
                         "everywhere)")
    ap.add_argument("--edges", default=None,
                    help="comma-separated edge counts (overrides the "
                         "smoke/full defaults)")
    ap.add_argument("--edge-factor", type=int, default=10,
                    help="edges per vertex (n_nodes = n_edges // factor)")
    ap.add_argument("--points", type=int, default=16,
                    help="capacity-sweep points (powers of two)")
    ap.add_argument("--ref-max-edges", type=int, default=2_000_000,
                    help="largest graph to run the per-capacity reference "
                         "on (it is the slow path being replaced)")
    ap.add_argument("--single-max-edges", type=int, default=10_000_000,
                    help="largest graph to run the single-host pipeline on "
                         "(above this only the sharded path runs; the "
                         "drift gate needs both)")
    ap.add_argument("--shards", type=int, default=None,
                    help="shard count for the sharded pipeline (default: "
                         "REPRO_TRACE_SHARDS, else the CPU count)")
    ap.add_argument("--engine", choices=("numpy", "jax"), default="numpy",
                    help="single-host amortized engine to time (jax = "
                         "jitted segment-sum path)")
    ap.add_argument("--repeats", type=int, default=5,
                    help="cold repetitions per timing; the minimum is "
                         "reported (steadies the wall clock against "
                         "scheduler noise)")
    ap.add_argument("--alpha", type=float, default=1.6)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.core import schedule_cache
    from repro.core.trace import (GraphTrace, clear_trace_cache,
                                  resolve_trace_dataset)
    from repro.data import synthetic
    from repro.distributed import trace_shard

    if args.edges is not None:
        edge_counts = [int(e) for e in args.edges.split(",")]
    elif args.smoke:
        edge_counts = [100_000, 300_000]
    else:
        edge_counts = [100_000, 1_000_000, 10_000_000, 100_000_000]

    n_shards = (args.shards if args.shards is not None
                else trace_shard.default_shard_count())
    repeats = max(1, args.repeats)
    rows = []
    failures: list[str] = []
    for n_edges in edge_counts:
        n_nodes = max(2, n_edges // args.edge_factor)
        caps = _pow2_caps(n_nodes, args.points)
        rss = {}

        # -- sharded pipeline (always): generation+sort, exchange, CSR --
        shard_stats: dict = {}
        strace = trace_shard.build_power_law_trace(
            n_nodes=n_nodes, n_edges=n_edges, seed=args.seed,
            alpha=args.alpha, n_shards=n_shards, stats=shard_stats)
        rss["shard_generate_sort_kb"] = shard_stats["rss_generate_sort_kb"]
        rss["shard_exchange_factorize_kb"] = (
            shard_stats["rss_exchange_factorize_kb"])
        rss["shard_csr_kb"] = shard_stats["rss_csr_kb"]

        t_sharded_sweep = None
        sharded_scheds = None
        for _ in range(repeats):
            strace.clear_schedules()  # factorization stays: timed above
            t0 = time.perf_counter()
            sharded_scheds = strace.schedules(caps, engine="sharded")
            dt = time.perf_counter() - t0
            t_sharded_sweep = (dt if t_sharded_sweep is None
                               else min(t_sharded_sweep, dt))
        rss["shard_sweep_kb"] = trace_shard._peak_rss_kb()
        t_total_sharded = (shard_stats["t_generate_sort_s"]
                           + shard_stats["t_exchange_factorize_s"]
                           + shard_stats["t_csr_s"] + t_sharded_sweep)

        errors = _check_schedules(strace, caps, sharded_scheds)

        # -- single-host pipeline + drift gates (bounded sizes) ----------
        run_single = n_edges <= args.single_max_edges
        t_generate = t_csr = t_amortized = t_reference = None
        t_total_single = None
        if run_single:
            t0 = time.perf_counter()
            snd, rcv = synthetic.power_law_edges(
                args.seed, n_nodes=n_nodes, n_edges=n_edges,
                alpha=args.alpha)
            t_generate = time.perf_counter() - t0
            rss["generate_kb"] = trace_shard._peak_rss_kb()

            t0 = time.perf_counter()
            trace = GraphTrace(snd, rcv, n_nodes)
            t_csr = time.perf_counter() - t0
            rss["csr_kb"] = trace_shard._peak_rss_kb()

            # Amortized engine, cold each repeat (a fresh trace drops the
            # shared factorization and schedule LRU, so every repetition
            # pays the one shared sort); minimum of the repeats reported.
            scheds = None
            for _ in range(repeats):
                cold = GraphTrace(snd, rcv, n_nodes)
                t0 = time.perf_counter()
                scheds = cold.schedules(caps, engine=args.engine)
                dt = time.perf_counter() - t0
                t_amortized = (dt if t_amortized is None
                               else min(t_amortized, dt))
            rss["sweep_kb"] = trace_shard._peak_rss_kb()
            t_total_single = t_generate + t_csr + t_amortized

            # Distributed drift gate 1: the sharded factorization is
            # bit-identical (values, order, dtypes) to the single-host
            # one for this shard count.
            u_snd, u_rcv, _, mp = trace._pair_factorization()
            su_snd, su_rcv, _, smp = strace._pair_factorization()
            errors += [f"factorization: {e}" for e in
                       trace_shard.factorization_drift(
                           (su_snd, su_rcv, smp), (u_snd, u_rcv, mp))]
            # Drift gate 2: sharded schedules == amortized engine.
            errors += _check_schedules(strace, caps, sharded_scheds, scheds,
                                       label="single-host amortized engine")

            if n_edges <= args.ref_max_edges:
                refs = None
                for _ in range(repeats):
                    t0 = time.perf_counter()
                    refs = [trace.schedule_reference(c) for c in caps]
                    dt = time.perf_counter() - t0
                    t_reference = (dt if t_reference is None
                                   else min(t_reference, dt))
                errors += _check_schedules(trace, caps, scheds, refs)
                # Drift gate 3: sharded schedules == PR-4 oracle.
                errors += _check_schedules(
                    strace, caps, sharded_scheds, refs,
                    label="schedule_reference oracle")

        failures.extend(f"E={n_edges}: {e}" for e in errors)

        row = {
            "n_edges": n_edges,
            "n_nodes": n_nodes,
            "n_capacities": len(caps),
            "capacities": caps,
            "engine": args.engine,
            "n_shards": shard_stats["n_shards"],
            "n_unique_pairs": shard_stats["n_unique_pairs"],
            "t_shard_generate_sort_s": shard_stats["t_generate_sort_s"],
            "t_shard_exchange_factorize_s": (
                shard_stats["t_exchange_factorize_s"]),
            "t_shard_csr_s": shard_stats["t_csr_s"],
            "t_sharded_sweep_s": t_sharded_sweep,
            "t_total_sharded_s": t_total_sharded,
            "t_generate_s": t_generate,
            "t_csr_s": t_csr,
            "t_amortized_sweep_s": t_amortized,
            "t_total_single_s": t_total_single,
            "t_reference_sweep_s": t_reference,
            "speedup_vs_reference": (None if t_reference is None
                                     else t_reference / t_amortized),
            "edges_per_sec": n_edges * len(caps) / t_sharded_sweep,
            "rss_peak_kb": rss,
            "drift_errors": errors,
        }
        rows.append(row)
        single_txt = ("-" if t_total_single is None
                      else f"{t_total_single:7.2f}s")
        ref_txt = ("-" if t_reference is None
                   else f"{row['speedup_vs_reference']:6.1f}x")
        print(f"E={n_edges:>9}  V={n_nodes:>8}  caps={len(caps):>2}  "
              f"shards={shard_stats['n_shards']}  "
              f"sharded={t_total_sharded:7.2f}s  single={single_txt}  "
              f"sweep={t_sharded_sweep:6.3f}s  old/ratio={ref_txt}"
              + ("  DRIFT" if errors else ""))

    # Disk-cache round trip for the largest single-host graph (only when
    # the cache is enabled and the graph clears the min-edges threshold).
    # The demo runs against a scratch directory so the "cold" resolve is
    # genuinely cold on every invocation — a user/CI cache dir would
    # already hold the entry from a previous run and silently report
    # warm-as-cold.
    disk = {"enabled": schedule_cache.cache_root() is not None,
            "min_edges": schedule_cache.min_cached_edges()}
    biggest = max([e for e in edge_counts if e <= args.single_max_edges],
                  default=0)
    if disk["enabled"] and biggest >= disk["min_edges"] > 0:
        import os
        import shutil
        import tempfile

        params = {"n_nodes": max(2, biggest // args.edge_factor),
                  "n_edges": biggest, "seed": args.seed,
                  "alpha": args.alpha}
        scratch = tempfile.mkdtemp(prefix="trace-scale-cache-")
        saved = os.environ.get("REPRO_TRACE_CACHE")
        os.environ["REPRO_TRACE_CACHE"] = scratch
        try:
            clear_trace_cache()
            t0 = time.perf_counter()
            resolve_trace_dataset("power_law_stream", params)
            disk["resolve_cold_s"] = time.perf_counter() - t0
            clear_trace_cache()
            t0 = time.perf_counter()
            warm = resolve_trace_dataset("power_law_stream", params)
            disk["resolve_warm_s"] = time.perf_counter() - t0
            # Warm resolves are lazy; charge the deferred factorization
            # finish + one schedule separately so laziness can't hide a
            # regression behind an untouched mmap.
            t0 = time.perf_counter()
            warm.schedule(max(2, params["n_nodes"] // 4))
            disk["warm_first_schedule_s"] = time.perf_counter() - t0
            clear_trace_cache()
        finally:
            if saved is None:
                os.environ.pop("REPRO_TRACE_CACHE", None)
            else:
                os.environ["REPRO_TRACE_CACHE"] = saved
            shutil.rmtree(scratch, ignore_errors=True)
        print(f"disk cache: resolve cold {disk['resolve_cold_s']:.3f}s "
              f"-> warm {disk['resolve_warm_s']:.4f}s (mmap-lazy; first "
              f"schedule +{disk['warm_first_schedule_s']:.3f}s)")

    if args.json is not None:
        payload = {
            "benchmark": "trace_scale",
            "smoke": bool(args.smoke),
            "engine": args.engine,
            "n_shards": n_shards,
            "repeats": repeats,
            "points": args.points,
            "edge_factor": args.edge_factor,
            "alpha": args.alpha,
            "seed": args.seed,
            "disk_cache": disk,
            "rows": rows,
            "drift_failures": failures,
        }
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"# wrote {args.json}")

    if failures:
        print("# SCHEDULE DRIFT DETECTED:")
        for e in failures:
            print(f"#   {e}")
        return 1
    return 0


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    raise SystemExit(main())
