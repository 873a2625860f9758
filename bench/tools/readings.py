#!/usr/bin/env python3
"""Read a new cell's compared numbers over many seeds, from the program or
from its control (``controls_gated.py``).

    python3 bench/tools/readings.py --workload <cell> --seconds <s> --seeds 1 2 3 [--control]

One process, one run per seed, each on the chip at the cell's own size
with a short window. Prints one JSON line per seed with the checks; the
largest program reading and the smallest control reading are what a
limit is set between. With ``--control`` it exits non-zero unless every
seed comes out not correct, as ``control.py`` does for the older cells.
The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)

    harness.prepare_environment()
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    import controls_gated

    benchmark = harness.load_json(harness.ROOT / "BENCHMARK.json")
    _, _, traffic = harness.resolve_cell(benchmark, args.workload)
    hook = (controls_gated.install(traffic["driver"]) if args.control
            else None)
    all_failed = True
    for seed in args.seeds:
        result = harness.run_cell(args.workload, seed, args.seconds, False,
                                  t_start=time.perf_counter(),
                                  driver_hook=hook)
        all_failed &= not result["correct"]
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control": args.control,
                          "correct": result["correct"],
                          "checks": result["checks"]}), flush=True)
    return 0 if all_failed or not args.control else 1


if __name__ == "__main__":
    sys.exit(main())
