"""Analytical-vs-trace halo gap: ``PYTHONPATH=src python -m benchmarks.trace_gap``.

The first result in this repo the paper could not produce: the paper's
composition layer estimates inter-tile halo traffic with the
random-partition expected cut ``E * (1 - 1/n_tiles)`` over uniform tiles,
while the §12 trace backend counts the exact per-tile unique remote
sources of a *real* edge list.  This benchmark sweeps the power-law
exponent of the synthetic preferential-attachment graph (the workload
imbalance the paper highlights) and quantifies, per (alpha, tile
capacity):

* the exact unique-remote-source halo vs the closed-form estimate (the
  estimate ignores both clustering and within-tile source dedup, so it
  overshoots more as hubs concentrate traffic);
* per-tile edge imbalance (max/mean destination edges — uniform tiles
  assume 1.0);
* the degree-aware cache hit fraction at the default L = K/10 split;
* end-to-end scenario totals for a reference dataflow both ways
  (uniform ``full`` scenario vs exact ``trace`` scenario through
  ``repro.api.evaluate_scenarios``).

Prints one CSV row per (alpha, capacity) and with ``--json`` writes
``BENCH_trace.json`` for PR-over-PR diffing.
"""

from __future__ import annotations

import argparse
import csv
import io
import json
import time


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--json", nargs="?", const="BENCH_trace.json",
                    default=None, metavar="PATH",
                    help="also write a summary JSON (default BENCH_trace.json)")
    ap.add_argument("--n-nodes", type=int, default=20000)
    ap.add_argument("--n-edges", type=int, default=120000)
    ap.add_argument("--alphas", default="0.5,1.0,1.5,2.0,2.5",
                    help="comma-separated power-law exponents to sweep")
    ap.add_argument("--tile-vertices", default="512,1024,2048",
                    help="comma-separated tile capacities")
    ap.add_argument("--dataflow", default="engn",
                    help="reference dataflow for the end-to-end totals")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.api import Scenario, evaluate_scenarios
    from repro.core.trace import resolve_trace_dataset

    alphas = [float(a) for a in args.alphas.split(",")]
    caps = [int(c) for c in args.tile_vertices.split(",")]

    t0 = time.perf_counter()
    rows = []
    scenarios = []
    for alpha in alphas:
        params = {"n_nodes": args.n_nodes, "n_edges": args.n_edges,
                  "seed": args.seed, "alpha": alpha}
        trace = resolve_trace_dataset("power_law", params)
        for cap in caps:
            sched = trace.schedule(cap)
            stats = sched.stats()
            rows.append({"alpha": alpha, "tile_vertices": cap, **stats})
            scenarios.append(Scenario.trace(
                args.dataflow, dataset="power_law",
                params={k: float(v) for k, v in params.items()},
                N=30.0, T=5.0, tile_vertices=float(cap),
                label=f"trace/a{alpha}/t{cap}"))
            scenarios.append(Scenario.full_graph(
                args.dataflow, V=float(args.n_nodes), E=float(args.n_edges),
                N=30.0, T=5.0, tile_vertices=float(cap),
                label=f"uniform/a{alpha}/t{cap}"))

    res = evaluate_scenarios(scenarios)
    for i, row in enumerate(rows):
        tr, un = res.results[2 * i], res.results[2 * i + 1]
        row["trace_total_bits"] = tr.total_bits
        row["uniform_total_bits"] = un.total_bits
        row["uniform_over_trace_total"] = un.total_bits / tr.total_bits
        row["trace_halo_bits"] = tr.breakdown["haloreload"]
        row["uniform_halo_bits"] = un.breakdown["haloreload"]
    elapsed = time.perf_counter() - t0

    cols = list(rows[0])
    buf = io.StringIO()
    w = csv.DictWriter(buf, fieldnames=cols)
    w.writeheader()
    for r in rows:
        w.writerow(r)
    print(f"# ==== analytical vs trace halo gap "
          f"(V={args.n_nodes}, E={args.n_edges}, {args.dataflow}) ====")
    print(buf.getvalue(), end="")
    worst = max(rows, key=lambda r: r["halo_estimate_over_exact"] or 0.0)
    if worst["halo_estimate_over_exact"] is None:
        # Every swept point collapsed to a single tile (capacity >= V):
        # zero halo on both sides, so there is no gap to report.
        print(f"# no inter-tile halo at any swept point ({elapsed:.2f}s)")
    else:
        print(f"# worst halo overestimate: "
              f"{worst['halo_estimate_over_exact']:.2f}x "
              f"at alpha={worst['alpha']}, "
              f"tile_vertices={worst['tile_vertices']} ({elapsed:.2f}s)")

    if args.json is not None:
        payload = {
            "benchmark": "trace_gap",
            "n_nodes": args.n_nodes,
            "n_edges": args.n_edges,
            "seed": args.seed,
            "dataflow": args.dataflow,
            "elapsed_s": elapsed,
            "rows": rows,
        }
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"# wrote {args.json}")
    return 0


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    raise SystemExit(main())
