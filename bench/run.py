#!/usr/bin/env python3
"""Run one benchmark cell on the chip this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout, one process per chip. The cell's
configuration, traffic and metrics are named in ``BENCHMARK.json``; see
``bench/harness.py`` for how they are found. Inputs and weights come from
``--seed``. The run sets up and warms every shape it uses, measures for
``--seconds`` (with ``--trace 1`` a traced window of at most the traffic's
``trace_seconds``), checks what the window produced against the plain
reference, and prints the result as the last line of standard output.

It exits non-zero, printing no result, when JAX finds no TPU, a TPU that
``bench/peaks.json`` does not list, or fewer chips than the cell asks for,
and when the program under test is not in the checkout.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    harness.prepare_environment()
    try:
        from repro.compile_cache import enable_compile_cache
    except ImportError as exc:
        print(f"bench: the program is not in this checkout ({exc})",
              file=sys.stderr)
        return 2
    enable_compile_cache()
    try:
        result = harness.run_cell(args.workload, args.seed, args.seconds,
                                  bool(args.trace), t_start=T_START)
    except harness.SetupError as exc:
        print(f"bench: {exc}", file=sys.stderr)
        return 1
    harness.print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
