"""Measured vs modeled: close the validation loop the paper left open.

The paper (Sec. III) could not validate its data-movement models — the
accelerators' simulators are closed-source.  Our TPU adaptation can: the
XLA-compiled Pallas programs are open ground truth.  This walkthrough pins
the ``spmm_tiled`` (fused) and ``spmm_unfused`` (HyGCN inter-phase
analogue) dataflows to byte measurements of their compiled kernels at a
few operating points, then shows the fusion claim as a *measured* delta:
the inter-phase buffer the fused kernel eliminates.

    PYTHONPATH=src python examples/conformance_walkthrough.py
"""

import jax

from repro.core import registry
from repro.core.conformance import (OperatingPoint, conformance_records,
                                    interphase_delta_records,
                                    summarize_records, verify_numerics)


def main() -> None:
    points = [
        OperatingPoint(256, 16, 8, 128, 128),
        OperatingPoint(512, 32, 8, 128, 256),
        OperatingPoint(256, 16, 8, 256, 256),   # single-block schedule
    ]

    print("dataflows with a runnable kernel analogue:",
          ", ".join(registry.runnable_names()), "\n")

    records = []
    for name in registry.runnable_names():
        spec = registry.get(name)
        analogue = spec.runnable_analogue()
        print(f"== {name}: analytical closed forms vs compiled "
              f"{analogue.__class__.__name__} ==")
        first_point_recs = []
        for pt in points:
            recs = conformance_records(spec, pt, analogue=analogue)
            records.extend(recs)
            if pt == points[0]:
                first_point_recs = recs
            worst = max((abs(r.ratio - 1.0) for r in recs
                         if not r.one_sided), default=0.0)
            print(f"  K={pt.K:4d} N={pt.N:3d} Bn={pt.Bn:3d} Bk={pt.Bk:3d}: "
                  f"{len(recs)} records, max |ratio-1| = {worst:.2e}")
        # the first point in detail: per-movement attribution
        for r in first_point_recs:
            if r.source == "block_schedule":
                print(f"    {r.movement:16} analytical={r.analytical_bytes:10.0f}B"
                      f" measured={r.measured_bytes:10.0f}B ratio={r.ratio:.4f}")
        print()

    print("== the fusion claim, measured (DESIGN.md §3/§10) ==")
    print("fused-minus-unfused HBM bytes vs the paper's eliminated")
    print("K*N*sigma write + P_s*N*sigma read inter-phase terms (P_s = K):")
    for pt in points:
        for r in interphase_delta_records(pt):
            records.append(r)
            print(f"  K={pt.K:4d} N={pt.N:3d} [{r.source:14}] "
                  f"eliminated={r.analytical_bytes:8.0f}B "
                  f"measured delta={r.measured_bytes:8.0f}B ratio={r.ratio:.4f}")

    print("\nexecuting both kernels once against the jnp oracle "
          f"(on {jax.default_backend()}; interpreted off the TPU):")
    err = verify_numerics(points[0])
    print(f"  max relative error = {err:.3e}")

    summary = summarize_records(records)
    status = "ALL WITHIN DECLARED TOLERANCE" if summary["all_ok"] else "FAILURES"
    print(f"\n{summary['n_ok']}/{summary['n_records']} records ok -> {status}")


if __name__ == "__main__":
    main()
