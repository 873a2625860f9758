"""Conformance runner: ``PYTHONPATH=src python -m benchmarks.conformance``.

Compiles the runnable kernel analogues of every registered dataflow across
the operating-point sweep, prints one CSV row per
:class:`~repro.core.conformance.ConformanceRecord` (analytical vs measured
bytes, ratio, declared tolerance), and exits non-zero if any record fails —
the command-line form of the guarantee in DESIGN.md §10.

``--json [PATH]`` additionally writes a machine-readable summary (default
``BENCH_conformance.json``, same top-level shape as ``BENCH_sweep.json``:
a ``benchmarks`` timing block, plus the per-record rows) so future PRs can
diff the measured trajectory.  ``--execute`` also runs the kernels against
the jnp oracle (compile-only by default): compiled on a TPU, in Pallas
interpret mode on the CPU (:func:`repro.kernels.resolve_interpret`).
``--points M`` truncates the sweep for smoke runs.
"""

from __future__ import annotations

import argparse
import csv
import io
import json
import sys
import time

#: ``--execute`` fails the run when the kernels' max relative error vs the
#: jnp oracle reaches this (same bar as tests/test_conformance.py).
NUMERICS_REL_TOL = 1e-5


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--json", nargs="?", const="BENCH_conformance.json",
                    default=None, metavar="PATH",
                    help="also write a summary JSON (default "
                         "BENCH_conformance.json)")
    ap.add_argument("--points", type=int, default=None, metavar="M",
                    help="truncate the operating-point sweep to M points")
    ap.add_argument("--execute", action="store_true",
                    help="also execute the kernels (compiled on a TPU, "
                         "interpreted on the CPU) against the jnp oracle at "
                         "each point")
    args = ap.parse_args(argv)

    from repro.core.conformance import (default_operating_points,
                                        run_conformance, summarize_records,
                                        verify_numerics)

    points = default_operating_points()
    if args.points is not None:
        points = points[:args.points]

    t0 = time.perf_counter()
    records = run_conformance(points=points)
    elapsed = time.perf_counter() - t0

    rows = [r.as_row() for r in records]
    cols = sorted({k for r in rows for k in r})
    buf = io.StringIO()
    w = csv.DictWriter(buf, fieldnames=cols)
    w.writeheader()
    for r in rows:
        w.writerow(r)
    print(f"# ==== conformance ({len(rows)} records, "
          f"{len(points)} operating points) ====")
    print(buf.getvalue())

    numerics = None
    numerics_ok = True
    if args.execute:
        numerics = max(verify_numerics(pt) for pt in points)
        numerics_ok = numerics < NUMERICS_REL_TOL
        print(f"# numerics max relative error vs jnp oracle: {numerics:.3e} "
              f"(tolerance {NUMERICS_REL_TOL:.0e})")

    summary = summarize_records(records)
    summary["elapsed_s"] = elapsed
    if numerics is not None:
        summary["numerics_max_rel_err"] = numerics
    print(f"# summary: {json.dumps(summary['by_dataflow'], sort_keys=True)}")

    if args.json is not None:
        payload = {
            "benchmarks": {
                "conformance": {
                    "us_per_call": 1e6 * elapsed / max(len(records), 1),
                    "n_rows": len(records),
                },
            },
            "conformance": summary,
            "records": rows,
        }
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"# wrote {args.json} ({len(rows)} records)")

    if not summary["all_ok"]:
        failing = [str(r) for r in records if not r.ok]
        print("# CONFORMANCE FAILURES:", *failing, sep="\n# ", file=sys.stderr)
        return 1
    if not numerics_ok:
        print(f"# NUMERICS FAILURE: max relative error {numerics:.3e} "
              f">= {NUMERICS_REL_TOL:.0e}", file=sys.stderr)
        return 1
    print("# all conformance records within declared tolerance")
    return 0


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    raise SystemExit(main())
