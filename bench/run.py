"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic and metrics are found by name from
``BENCHMARK.json`` at the root of the checkout. The run needs the TPU
chips the cell asks for and exits non-zero, printing no result, without
them. It sets up (builds the cluster and jobs from the seed, fills the
cluster, compiles or loads every device program the cell's calls use),
measures for ``--seconds`` (no unit starts after that; the window ends
when the last unit started completes), then checks the window's answers
against the plain reference. The last line of standard output is the
result as one JSON object. With ``--trace 1`` the window runs under the
JAX profiler and the line holds the per-layer metrics instead of the
end-to-end ones.

``--rehearse --units N`` runs the cell on the CPU (``JAX_PLATFORMS=cpu``)
for N units instead of a timed window and prints only its work counts:
events, simulator calls, messages per call and message-hops per
placement scored. It prints no metric and no result line.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(os.path.dirname(HERE), "src"), HERE]


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal: print work counts only")
    ap.add_argument("--units", type=int, default=0,
                    help="units to run in a rehearsal (instead of a timed window)")
    args = ap.parse_args(argv)
    if args.rehearse and args.units <= 0:
        ap.error("--rehearse needs --units N")
    return args


def main(argv=None) -> int:
    args = parse(argv)
    import repro  # noqa: F401  (fails here, with no result, without the program)
    from harness.core import run_cell
    out = run_cell(args, T_START)
    if not args.rehearse:
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
