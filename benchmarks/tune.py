"""Auto-tuner benchmark: ``PYTHONPATH=src python -m benchmarks.tune``.

Gates the §15 design-space auto-tuner's two load-bearing claims:

* **Oracle parity** — on three small search spaces (uniform full-graph
  with residency/capacity axes, uniform with n_tiles/halo axes, and a
  trace tune over the molecule batch) the tuner's winner and every
  evaluated point must be bit-identical to an independent brute force
  (per-candidate planner calls + masked ``np.argmin``).
* **Amortized search at scale** — a 16-point power-of-two capacity
  sweep x all registered dataflows over a 10⁶-edge streaming power-law
  trace must finish within the 5 s CPU budget and perform exactly ONE
  sorted-edge factorization and ONE trace build
  (``trace_cache_info()["stats"]``): capacities batch along the
  planner axis and every dataflow shares the per-capacity schedule LRU.

Pareto sanity (strictly shaped, pairwise non-dominated frontier) rides
along on the big tune.  Disk caching is disabled up front so the
counters measure the in-process machinery, not a warm
``~/.cache/repro-trace``.  Outputs one row per tune and with ``--json``
writes ``BENCH_tune.json`` for PR-over-PR diffing; exits non-zero on
any gate failure (the CI ``tune-smoke`` job runs ``--smoke``).
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

os.environ["REPRO_TRACE_CACHE"] = "0"  # before any trace resolution

import numpy as np

TIME_BUDGET_S = 5.0


def _pow2_caps(n_nodes: int, points: int) -> list[int]:
    caps: list[int] = []
    i = 1
    while len(caps) < points:
        cap = max(1, n_nodes >> i)
        if caps and cap == caps[-1]:
            break
        caps.append(cap)
        i += 1
    return caps


def _oracle_gate(scenario) -> list[str]:
    """Brute-force the space independently; return drift messages."""
    from repro.api import Composition, evaluate_scenario
    from repro.core import registry, tile_working_set_bits, tune_scenario

    opt = scenario.optimize
    space = opt["space"]
    comp = scenario.composition
    if scenario.graph_kind == "trace":
        from repro.core import resolve_trace_dataset
        V = float(resolve_trace_dataset(scenario.graph["dataset"],
                                        scenario.graph["params"]).n_nodes)
    else:
        V = float(scenario.graph["V"])
    dataflows = (registry.names() if space.get("dataflow") == "all"
                 else tuple(space.get("dataflow") or (scenario.dataflow,)))
    residencies = tuple(space.get("residency") or (comp.residency,))
    halos = tuple(space.get("halo_dedup") or (comp.halo_dedup,))
    if "tile_vertices" in space:
        caps = tuple(space["tile_vertices"])
    elif "n_tiles" in space:
        caps = tuple(float(math.ceil(V / nt)) for nt in space["n_tiles"])
    else:
        caps = (float(comp.tile_vertices),)

    objs, srams = [], []
    for df in dataflows:
        sigma = float(scenario.hardware.get(
            "sigma", registry.get(df).hw_factory().sigma))
        for res in residencies:
            for hd in halos:
                for cap in caps:
                    r = evaluate_scenario(scenario.replace(
                        dataflow=df, optimize=None, expect=None,
                        composition=Composition(
                            widths=comp.widths, residency=res,
                            tile_vertices=cap, halo_dedup=hd)))
                    objs.append(float(r.total_bits))
                    srams.append(float(tile_working_set_bits(
                        cap, V=V, widths=(comp.widths
                                          or (scenario.graph["N"],
                                              scenario.graph["T"])),
                        sigma=sigma, residency=res, halo_dedup=hd)))
    best = int(np.argmin(objs))

    tr = tune_scenario(scenario)
    drift = []
    if tr.method != "exhaustive":
        drift.append(f"expected exhaustive sweep, got {tr.method}")
    if tr.n_evaluated != len(objs):
        drift.append(f"evaluated {tr.n_evaluated} points, oracle enumerates "
                     f"{len(objs)}")
    for i, p in enumerate(tr.points):
        if p.index != i or p.objective != objs[i] or p.sram_bits != srams[i]:
            drift.append(f"point {i}: tuner ({p.objective}, {p.sram_bits}) "
                         f"!= oracle ({objs[i]}, {srams[i]})")
    if tr.best.index != best or tr.best.objective != objs[best]:
        drift.append(f"winner: tuner #{tr.best.index} ({tr.best.objective}) "
                     f"!= oracle #{best} ({objs[best]})")
    return drift


def _pareto_gate(tr) -> list[str]:
    drift = []
    fr = tr.frontier
    if not fr:
        return ["empty Pareto frontier on an open-budget tune"]
    for a, b in zip(fr, fr[1:]):
        if not (a.sram_bits < b.sram_bits and a.objective > b.objective):
            drift.append(f"frontier not strictly shaped at sram="
                         f"{b.sram_bits:g}")
    feas = [p for p in tr.points if p.feasible]
    for p in fr:
        for q in feas:
            if q.sram_bits <= p.sram_bits and q.objective < p.objective:
                drift.append(f"frontier point #{p.index} dominated by "
                             f"#{q.index}")
    if fr[-1].objective != tr.best.objective:
        drift.append("frontier does not end at the unconstrained winner")
    return drift


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="CI budget: 10⁴-edge trace, 8 capacities")
    ap.add_argument("--edges", type=float, default=None,
                    help="override trace edge count (default 1e6 / 1e4)")
    ap.add_argument("--points", type=int, default=None,
                    help="capacity sweep points (default 16 / 8)")
    ap.add_argument("--json", nargs="?", const="BENCH_tune.json",
                    default=None, metavar="PATH")
    args = ap.parse_args(argv)

    from repro.api import Scenario
    from repro.core import (clear_trace_cache, registry, reset_trace_stats,
                            trace_cache_info, tune_scenario)

    n_edges = int(args.edges if args.edges is not None
                  else (1e4 if args.smoke else 1e6))
    points = args.points if args.points is not None else (8 if args.smoke
                                                          else 16)
    n_nodes = 1 << max(8, int(math.log2(max(n_edges // 8, 256))))
    caps = _pow2_caps(n_nodes, points)
    failures: list[str] = []
    report: dict = {"smoke": bool(args.smoke), "gates": {}}

    # -- gate 1: oracle parity on three small spaces -----------------------
    oracle_spaces = [
        ("uniform-residency-capacity", Scenario.full_graph(
            registry.names()[0], V=512.0, E=4096.0, N=64.0, T=8.0,
            widths=(64, 16, 8), tile_vertices=128.0,
            label="tune-bench-uniform",
            optimize={"objective": "movement",
                      "space": {"dataflow": "all",
                                "tile_vertices": [64, 128, 256, 512],
                                "residency": ["spill", "resident"]}})),
        ("uniform-ntiles-halo", Scenario.full_graph(
            registry.names()[0], V=2048.0, E=20480.0, N=32.0, T=8.0,
            widths=(32, 16, 8), tile_vertices=256.0,
            label="tune-bench-halo",
            optimize={"objective": "movement",
                      "space": {"n_tiles": [1, 2, 4, 8],
                                "halo_dedup": [1.0, 2.0, 4.0]}})),
        ("trace-molecule", Scenario.trace(
            registry.names()[0], dataset="molecule",
            params={"batch": 8, "n_nodes": 30, "n_edges": 64, "seed": 0,
                    "step": 0},
            N=16.0, T=16.0, widths=(16, 16, 16), tile_vertices=32.0,
            label="tune-bench-trace",
            optimize={"objective": "movement",
                      "space": {"dataflow": "all",
                                "tile_vertices": [16, 32, 64]}})),
    ]
    t0 = time.perf_counter()
    for name, s in oracle_spaces:
        drift = _oracle_gate(s)
        report["gates"][f"oracle:{name}"] = {"ok": not drift, "drift": drift}
        failures += [f"oracle:{name}: {d}" for d in drift]
        print(f"oracle parity [{name}]: {'OK' if not drift else 'DRIFT'}")
    report["oracle_seconds"] = round(time.perf_counter() - t0, 3)

    # -- gate 2: 16-capacity x all-dataflow tune over a big trace ----------
    big = Scenario.trace(
        registry.names()[0], dataset="power_law_stream",
        params={"alpha": 1.6, "n_nodes": float(n_nodes),
                "n_edges": float(n_edges), "seed": 0},
        N=64.0, T=16.0, widths=(64, 32, 16), tile_vertices=float(caps[0]),
        label=f"tune-bench-powerlaw-{n_edges:g}",
        optimize={"objective": "movement",
                  "space": {"dataflow": "all",
                            "tile_vertices": [float(c) for c in caps]}})
    clear_trace_cache()
    reset_trace_stats()
    t0 = time.perf_counter()
    tr = tune_scenario(big)
    tune_s = time.perf_counter() - t0
    stats = trace_cache_info()["stats"]

    n_df = len(registry.names())
    print(f"big tune: {n_edges:g} edges, {len(caps)} capacities x {n_df} "
          f"dataflows = {tr.n_candidates} candidates in {tune_s:.2f}s "
          f"({tr.n_groups} broadcast groups)")
    print(f"  best: {tr.best.dataflow} tv={tr.best.tile_vertices:g} "
          f"obj={tr.best.objective:.6g} bits "
          f"(frontier: {len(tr.frontier)} points)")
    print(f"  trace stats: {stats}")

    gate = {"seconds": round(tune_s, 3), "stats": dict(stats),
            "n_candidates": tr.n_candidates, "n_groups": tr.n_groups}
    if stats["factorizations"] != 1:
        failures.append(f"big tune ran {stats['factorizations']} "
                        "factorizations; the whole sweep must share ONE")
    if stats["trace_builds"] != 1:
        failures.append(f"big tune ran {stats['trace_builds']} trace builds")
    if tr.n_candidates != len(caps) * n_df:
        failures.append(f"expected {len(caps) * n_df} candidates, "
                        f"evaluated {tr.n_candidates}")
    if not args.smoke and tune_s > TIME_BUDGET_S:
        failures.append(f"big tune took {tune_s:.2f}s "
                        f"(budget {TIME_BUDGET_S:g}s)")
    gate["ok"] = not any(f.startswith("big tune") or "candidates" in f
                         for f in failures)
    report["gates"]["big-tune"] = gate
    report["big_tune"] = tr.to_dict()
    report["big_tune"].pop("points", None)  # keep the JSON diffable

    # -- gate 3: Pareto sanity on the big tune -----------------------------
    drift = _pareto_gate(tr)
    report["gates"]["pareto"] = {"ok": not drift, "drift": drift}
    failures += [f"pareto: {d}" for d in drift]
    print(f"pareto frontier: {'OK' if not drift else 'DRIFT'}")

    report["status"] = "ok" if not failures else "failed"
    for f in failures:
        print(f"# GATE FAILURE: {f}", file=sys.stderr)
    if args.json is not None:
        with open(args.json, "w") as fh:
            json.dump(report, fh, indent=2, sort_keys=True)
            fh.write("\n")
        print(f"# wrote {args.json}")
    return 1 if failures else 0


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    raise SystemExit(main())
