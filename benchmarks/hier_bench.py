"""Hierarchy benchmark: mapping strategies vs rack oversubscription.

Sweeps the fat-tree oversubscription ratio of the ``rack_oversub``
cluster (DESIGN.md §9) and replays the same Poisson arrival trace
through ``repro.sched.FleetScheduler`` once per mapping strategy. At
ratio 1.0 the rack uplinks carry full bisection bandwidth and the level
hierarchy barely matters; as the ratio grows the rack uplink becomes the
scarce resource and hierarchy-aware placement (``recursive_bisect``)
pulls away from the flat strategies.

    PYTHONPATH=src python benchmarks/hier_bench.py --out BENCH_hier.json
    PYTHONPATH=src python benchmarks/hier_bench.py --quick   # CI smoke gate

``--quick`` runs one ratio with a short trace and exits non-zero unless
(a) ``recursive_bisect`` beats every other strategy on total message
wait and (b) the scheduler's core accounting survives the run.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys

from repro import obs
from repro.compile_cache import enable_compile_cache
from repro.sched import (FleetScheduler, RemapConfig, SchedulerConfig,
                         get_trace)

STRATEGIES = ("blocked", "cyclic", "drb", "new", "recursive_bisect")


def run_ratio(oversub: float, strategies=STRATEGIES, *, n_arrivals: int = 24,
              rate: float = 0.5, seed: int = 0,
              remap_interval: float | None = 5.0,
              sim_backend: str = "auto") -> dict:
    results: dict[str, dict] = {}
    for strategy in strategies:
        spec = get_trace("rack_oversub", seed=seed, rate=rate,
                         n_arrivals=n_arrivals, oversub=oversub)
        sched = FleetScheduler(
            spec.cluster, strategy,
            config=SchedulerConfig(
                remap=RemapConfig(interval=remap_interval),
                state_bytes_per_proc=spec.state_bytes_per_proc,
                count_scale=spec.count_scale,
                sim_backend=sim_backend))
        sched.submit_trace(spec.arrivals)
        stats = sched.run()
        sched.check_invariants()
        results[strategy] = {
            "total_msg_wait": stats.total_msg_wait,
            "makespan": stats.makespan,
            "total_queue_wait": stats.total_queue_wait,
            "level_p99_util": stats.level_p99_util,
            "n_remap_commits": stats.n_remap_commits,
        }
    def wait(s):
        return results[s]["total_msg_wait"]
    rb = wait("recursive_bisect") if "recursive_bisect" in results else None
    return {
        "oversub": oversub,
        "strategies": results,
        "rb_beats_all": bool(
            rb is not None and all(rb < wait(s) for s in results
                                   if s != "recursive_bisect")),
        "rb_gain_vs_best_other": (
            round(1.0 - rb / min(wait(s) for s in results
                                 if s != "recursive_bisect"), 4)
            if rb is not None and len(results) > 1 else None),
    }


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--ratios", nargs="+", type=float,
                    default=[1.0, 2.0, 4.0, 8.0],
                    help="rack oversubscription ratios to sweep")
    ap.add_argument("--strategies", nargs="+", default=list(STRATEGIES))
    ap.add_argument("--arrivals", type=int, default=24)
    ap.add_argument("--rate", type=float, default=0.5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--remap-interval", type=float, default=5.0)
    ap.add_argument("--no-remap", action="store_true")
    ap.add_argument("--sim-backend", default="auto")
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke: one ratio, short trace, hard assertions")
    ap.add_argument("--trace", action="store_true",
                    help="record a flight-recorder trace (repro.obs) of the "
                         "sweep to --trace-out")
    ap.add_argument("--trace-out", default="TRACE_hier.json")
    ap.add_argument("--out", default=None, help="also write JSON here")
    args = ap.parse_args(argv)
    enable_compile_cache()

    ratios = [4.0] if args.quick else args.ratios
    n_arrivals = 12 if args.quick else args.arrivals
    report = {"trace": "rack_oversub",
              "params": {"rate": args.rate, "n_arrivals": n_arrivals,
                         "seed": args.seed, "sim_backend": args.sim_backend},
              "sweep": []}
    recorder = obs.Recorder() if args.trace else obs.from_env()
    ctx = (obs.recording(recorder) if recorder is not None
           else contextlib.nullcontext())
    with ctx:
        for ratio in ratios:
            if recorder is not None:
                recorder.set_process(f"hier:oversub{ratio:g}")
            row = run_ratio(ratio, tuple(args.strategies),
                            n_arrivals=n_arrivals, rate=args.rate,
                            seed=args.seed,
                            remap_interval=None if args.no_remap
                            else args.remap_interval,
                            sim_backend=args.sim_backend)
            report["sweep"].append(row)
            msg = "  ".join(f"{s}={r['total_msg_wait']:.0f}s"
                            for s, r in row["strategies"].items())
            print(f"oversub {ratio:4.1f}: {msg}  "
                  f"rb_beats_all={row['rb_beats_all']}", file=sys.stderr)
    if recorder is not None:
        with open(args.trace_out, "w") as f:
            f.write(recorder.dump_json())
        print(f"trace: {recorder.n_events()} events -> {args.trace_out}",
              file=sys.stderr)

    text = json.dumps(report, indent=1, sort_keys=True)
    print(text)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text)
    if args.quick:
        fails = [f"oversub {row['oversub']}: recursive_bisect did not win "
                 f"(gain vs best other: {row['rb_gain_vs_best_other']})"
                 for row in report["sweep"] if not row["rb_beats_all"]]
        for m in fails:
            print(f"SMOKE FAIL: {m}", file=sys.stderr)
        if fails:
            raise SystemExit(1)


if __name__ == "__main__":
    main()
