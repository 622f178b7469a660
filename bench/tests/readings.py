"""Correctness readings of one cell over many seeds, in one process.

    python3 bench/tests/readings.py --workload <cell> --seconds <s> --seeds <n> [<n> ...] [--control]

Runs the cell as ``bench/run.py`` would (on the chip, at the cell's own
size and load, with a short window) once per seed and prints, per seed,
every number the check compares beside its limit. One process serves
all seeds, so set-up and compilation are paid once.

``--control`` switches the program's own float32 Pallas scan on
(``REPRO_SIM_BACKEND=pallas``) in place of its float64 one: the nearest
precision below the float64 the configurations state. Every control
reading must fail the cell's limit on at least one of the numbers.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [os.path.join(os.path.dirname(BENCH), "src"), BENCH]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--keep-trace", default=None,
                    help="trace the first seed's window and keep its .xplane.pb here")
    a = ap.parse_args()
    if a.control:
        os.environ["REPRO_SIM_BACKEND"] = "pallas"
    from harness.core import run_cell
    label = "control" if a.control else "program"
    for i, seed in enumerate(a.seeds):
        keep = a.keep_trace if i == 0 else None
        args = argparse.Namespace(workload=a.workload, seed=seed, seconds=a.seconds,
                                  trace=int(bool(keep)), rehearse=False, units=0)
        t0 = time.perf_counter()
        out = run_cell(args, t0, keep_trace=keep)
        nums = " ".join(f"{k}={v['value']!r}(limit {v['limit']!r})" for k, v in out["check"].items())
        print(f"reading: {label} cell={a.workload} seed={seed} units={out['attempted']} "
              f"{nums} correct={out['correct']} run_s={time.perf_counter() - t0:.1f}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
