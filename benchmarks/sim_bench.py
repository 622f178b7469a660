"""Simulator backend shoot-out: loop vs scan backends (DESIGN.md §8).

Builds the live workload the online scheduler actually simulates — every
job of a named trace admitted until the cluster is full — then measures
each backend on three axes:

1. ``simulate()`` throughput (messages/sec, speedup vs the PR-1
   per-server-loop baseline) with agreement checks on ``total_wait``;
2. ``simulate_batch()`` of K trial placements (the remap pass's batched
   candidate evaluation) vs K individual calls;
3. end-to-end ``sched_bench`` wall-clock for the same trace, loop vs the
   default scan backend.

    PYTHONPATH=src python benchmarks/sim_bench.py --out BENCH_sim.json
    PYTHONPATH=src python benchmarks/sim_bench.py --quick   # CI smoke gate

``--quick`` shrinks repeats and exits non-zero unless (a) every backend
agrees with the loop baseline within tolerance and (b) the segmented path
is at least as fast as the loop path.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time

import numpy as np

from repro import obs
from repro.compile_cache import enable_compile_cache
from repro.core.simulator import resolve_backend, simulate, simulate_batch
from repro.sched import (FleetScheduler, SchedulerConfig, get_trace,
                         trace_names)

# agreement tolerance vs the loop baseline, per backend (f64 / f64 / f32)
TOLERANCES = {"segmented": 1e-9, "jax": 1e-6, "pallas": 1e-3}


def live_workload(trace_name: str, seed: int = 0):
    """Admit trace arrivals until the cluster is full — a live snapshot."""
    spec = get_trace(trace_name, seed=seed)
    sched = FleetScheduler(spec.cluster, "new", config=SchedulerConfig(
        count_scale=spec.count_scale))
    for a in spec.arrivals:
        if a.graph.n_procs <= sched.tracker.total_free():
            sched.admit(a.graph)
    jobs = [j.graph for j in sched.live.values()]
    return spec, jobs, sched.placement


def _best_time(fn, repeats: int) -> float:
    """min over repeats — scheduler/OS noise is strictly additive."""
    fn()                                     # warm caches / compile
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    return min(times)


def _best_times_interleaved(fns: dict, repeats: int) -> dict:
    """min-of-N per labelled fn, round-robin so every fn sees the same
    background-load conditions — keeps the RATIOS honest on noisy hosts."""
    for fn in fns.values():                  # warm caches / compile
        fn()
    best = {name: float("inf") for name in fns}
    for _ in range(repeats):
        for name, fn in fns.items():
            t0 = time.perf_counter()
            fn()
            best[name] = min(best[name], time.perf_counter() - t0)
    return best


def trial_placements(jobs, placement, k: int, seed: int = 0):
    """K deterministic trial moves: permute one job's cores per trial."""
    rng = np.random.default_rng(seed)
    trials = []
    ids = sorted(placement.assignments)
    for i in range(k):
        p = placement.copy()
        jid = ids[i % len(ids)]
        cores = p.assignments[jid].copy()
        rng.shuffle(cores)
        p.assign(jid, cores)
        trials.append(p)
    return trials


def run(trace_name: str, backends, repeats: int, batch_k: int,
        sched_arrivals: int, skip_sched: bool) -> dict:
    spec, jobs, placement = live_workload(trace_name)
    sim_args = (jobs, placement, spec.cluster)
    kw = dict(count_scale=spec.count_scale)

    base = simulate(*sim_args, backend="loop", **kw)
    report: dict = {
        "trace": trace_name,
        "n_jobs": len(jobs),
        "n_messages": base.n_messages,
        "auto_backend": resolve_backend("auto"),
        "backends": {},
    }

    def _runner(be):
        return lambda: simulate(*sim_args, backend=be, **kw)

    secs = _best_times_interleaved(
        {be: _runner(be) for be in ("loop", *backends)}, repeats)
    loop_sec = secs["loop"]
    report["backends"]["loop"] = {
        "sec_per_call": loop_sec,
        "msgs_per_sec": base.n_messages / loop_sec,
        "total_wait": base.total_wait,
    }
    for be in backends:
        res = simulate(*sim_args, backend=be, **kw)
        rel_err = abs(res.total_wait - base.total_wait) / base.total_wait
        report["backends"][be] = {
            "sec_per_call": secs[be],
            "msgs_per_sec": base.n_messages / secs[be],
            "total_wait": res.total_wait,
            "rel_err_vs_loop": rel_err,
            "agrees": bool(rel_err <= TOLERANCES[be]),
            "speedup_vs_loop": loop_sec / secs[be],
        }

    # batched candidate evaluation (remap-pass shape)
    trials = trial_placements(jobs, placement, batch_k)
    batch_backend = "jax" if "jax" in backends else "segmented"
    single_sec = _best_time(
        lambda: [simulate(jobs, p, spec.cluster, backend=batch_backend, **kw)
                 for p in trials], max(1, repeats // 2))
    batch_sec = _best_time(
        lambda: simulate_batch(jobs, trials, spec.cluster,
                               backend=batch_backend, **kw),
        max(1, repeats // 2))
    report["batch"] = {
        "backend": batch_backend,
        "k": batch_k,
        "sec_batched": batch_sec,
        "sec_individual": single_sec,
        "speedup": single_sec / batch_sec,
    }

    if not skip_sched:
        from sched_bench import run_trace
        sched = {}
        for be in ("loop", "segmented"):
            t0 = time.perf_counter()
            run_trace(trace_name, ("new",), n_arrivals=sched_arrivals,
                      remap_interval=5.0, sim_backend=be)
            sched[be] = time.perf_counter() - t0
        report["sched_bench"] = {
            "n_arrivals": sched_arrivals,
            "wall_s_loop": sched["loop"],
            "wall_s_segmented": sched["segmented"],
            "speedup": sched["loop"] / sched["segmented"],
        }
    return report


def _gate(report: dict) -> list[str]:
    """CI assertions for --quick; returns failure messages."""
    fails = []
    for be, r in report["backends"].items():
        if be != "loop" and not r["agrees"]:
            fails.append(f"{be} disagrees with loop: "
                         f"rel_err={r['rel_err_vs_loop']:.3e}")
    seg = report["backends"].get("segmented")
    if seg and seg["speedup_vs_loop"] < 1.0:
        fails.append(f"segmented slower than loop "
                     f"({seg['speedup_vs_loop']:.2f}x)")
    return fails


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--scenario", default="table4_poisson",
                    choices=trace_names(), help="named arrival trace")
    ap.add_argument("--trace", action="store_true",
                    help="record a flight-recorder trace of the measured "
                         "runs (repro.obs) to --trace-out")
    ap.add_argument("--trace-out", default="TRACE_sim.json")
    ap.add_argument("--backends", nargs="+",
                    default=["segmented", "jax"],
                    choices=["segmented", "jax", "pallas"])
    ap.add_argument("--repeats", type=int, default=10)
    ap.add_argument("--batch-k", type=int, default=6,
                    help="candidate placements per simulate_batch call")
    ap.add_argument("--sched-arrivals", type=int, default=16,
                    help="trace length for the end-to-end sched_bench timing")
    ap.add_argument("--skip-sched", action="store_true",
                    help="skip the end-to-end sched_bench wall-clock runs")
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke mode: fewer repeats + hard assertions")
    ap.add_argument("--out", default=None, help="also write JSON here")
    args = ap.parse_args(argv)

    enable_compile_cache()
    repeats = 3 if args.quick else args.repeats
    recorder = obs.Recorder() if args.trace else obs.from_env()
    ctx = (obs.recording(recorder) if recorder is not None
           else contextlib.nullcontext())
    with ctx:
        report = run(args.scenario, args.backends, repeats, args.batch_k,
                     args.sched_arrivals, args.skip_sched)
    if recorder is not None:
        with open(args.trace_out, "w") as f:
            f.write(recorder.dump_json())
        print(f"trace: {recorder.n_events()} events -> {args.trace_out}",
              file=sys.stderr)

    for be, r in report["backends"].items():
        extra = ("" if be == "loop" else
                 f"  {r['speedup_vs_loop']:5.2f}x vs loop  "
                 f"agree={r['agrees']}")
        print(f"{be:10s} {r['sec_per_call']*1e3:8.2f} ms/call  "
              f"{r['msgs_per_sec']:12,.0f} msgs/s{extra}", file=sys.stderr)
    if "sched_bench" in report:
        sb = report["sched_bench"]
        print(f"sched_bench e2e: loop {sb['wall_s_loop']:.2f}s -> "
              f"segmented {sb['wall_s_segmented']:.2f}s "
              f"({sb['speedup']:.2f}x)", file=sys.stderr)

    text = json.dumps(report, indent=1, sort_keys=True)
    print(text)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text)
    if args.quick:
        fails = _gate(report)
        for msg in fails:
            print(f"SMOKE FAIL: {msg}", file=sys.stderr)
        if fails:
            raise SystemExit(1)


if __name__ == "__main__":
    main()
