"""Benchmark harness entry point: ``PYTHONPATH=src python -m benchmarks.run``.

Prints one CSV per paper table/figure (name,us_per_call,derived columns)
followed by the §Roofline table derived from the dry-run artifacts (if
present).  Every benchmark is a scenario batch through the ``repro.api``
front door (DESIGN.md §11) — the figures via their named templates, the
composition and workload studies as explicit batches; ``python -m
repro.api`` replays any of them from JSON.  Use ``--figure figN`` (fig3..
fig7, sweep_all, cora_end_to_end, workloads) / ``--skip-roofline`` to
subset, and ``--json [PATH]`` to additionally emit a machine-readable
timing summary (default ``BENCH_sweep.json``) covering fig3-fig7 plus the
all-accelerator, full-graph composition, and workload-bridge sweeps —
future PRs diff this file for the sweep engine's perf trajectory.  The
JSON also carries a ``conformance`` block (one small measured-vs-modeled
operating point, DESIGN.md §10); ``--skip-conformance`` drops it, and
``python -m benchmarks.conformance`` runs the full sweep.  An
``analysis`` block summarizes the static model audit (DESIGN.md §16:
per-dataflow unit/dead-hw/overflow counts, lint violations, mutation
battery); ``--skip-analysis`` drops it, and ``python -m repro.analysis``
is the full gate.
"""

from __future__ import annotations

import argparse
import csv
import io
import json


def _print_csv(name: str, rows: list[dict]) -> None:
    if not rows:
        print(f"# {name}: no rows")
        return
    cols = sorted({k for r in rows for k in r})
    buf = io.StringIO()
    w = csv.DictWriter(buf, fieldnames=cols)
    w.writeheader()
    for r in rows:
        w.writerow(r)
    print(f"# ==== {name} ({len(rows)} rows) ====")
    print(buf.getvalue())


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--figure", default=None,
                    help="only this benchmark (fig3..fig7, sweep_all, "
                         "cora_end_to_end, workloads)")
    ap.add_argument("--skip-roofline", action="store_true")
    ap.add_argument("--skip-conformance", action="store_true",
                    help="omit the conformance summary block from --json")
    ap.add_argument("--skip-analysis", action="store_true",
                    help="omit the model-audit summary block from --json")
    ap.add_argument("--json", nargs="?", const="BENCH_sweep.json", default=None,
                    metavar="PATH",
                    help="also write a timing summary JSON (default "
                         "BENCH_sweep.json)")
    args = ap.parse_args()

    from . import paper_figures

    summary: dict[str, dict] = {}
    for fn in paper_figures.ALL:
        if args.figure and fn.__name__ != args.figure:
            continue
        rows = fn()
        _print_csv(fn.__name__, rows)
        # Keyed by the per-row figure label so independently-timed
        # sub-benchmarks (fig5 times engn and hygcn separately) each keep
        # their own perf-trajectory entry.
        for r in rows:
            entry = summary.setdefault(
                str(r.get("figure", fn.__name__)),
                {"us_per_call": r.get("us_per_call"), "n_rows": 0})
            entry["n_rows"] += 1

    if args.json is not None:
        payload = {"benchmarks": summary}
        if not args.skip_conformance:
            from repro.core.conformance import (OperatingPoint,
                                                run_conformance,
                                                summarize_records)
            records = run_conformance(
                points=(OperatingPoint(256, 16, 8, 128, 128),))
            payload["conformance"] = summarize_records(records)
        if not args.skip_analysis:
            from repro.analysis import (audit_registry, lint_paths,
                                        run_mutation_battery)
            audits = audit_registry()
            outcomes = run_mutation_battery()
            payload["analysis"] = {
                "dataflows": {
                    name: {"ok": a.ok,
                           "unit_errors": a.unit_error_count,
                           "waived_unit_issues": a.waived_issue_count,
                           "overflow_findings": a.overflow_count,
                           "dead_hw": list(a.dead_hw),
                           "waived_dead_hw": list(a.waived_dead_hw)}
                    for name, a in sorted(audits.items())},
                "lint_violations": len(lint_paths()),
                "mutants_caught": sum(o.caught for o in outcomes),
                "mutants_total": len(outcomes),
            }
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"# wrote {args.json} ({len(summary)} benchmarks)")

    if not args.skip_roofline and not args.figure:
        from . import roofline
        try:
            table_rows = roofline.rows("single")
        except FileNotFoundError:
            table_rows = []
        if table_rows:
            _print_csv("roofline_single_pod", table_rows)
            print("# roofline table (human-readable):")
            print(roofline.render_table())
        else:
            print("# roofline: no dry-run artifacts "
                  "(run PYTHONPATH=src python -m repro.launch.dryrun)")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
