"""Online-scheduler strategy shoot-out under dynamic arrival traces.

Extends the paper's static Tables 2–5 comparison to the regime it was
written for but never measured: jobs arriving/departing on a shared
cluster (DESIGN.md §3). For each mapping strategy the same Poisson trace
is replayed through ``repro.sched.FleetScheduler`` and the run is scored
on makespan, total queue wait, total simulated message wait and the p99
of per-node NIC utilisation.

    PYTHONPATH=src python benchmarks/sched_bench.py --scenario table4_poisson
    PYTHONPATH=src python benchmarks/sched_bench.py --scenario serve_fleet \
        --strategies new new_tpu cyclic
    PYTHONPATH=src python benchmarks/sched_bench.py --quick  # CI smoke gate

The scheduler re-clocks every live job's departure after each fleet
mutation (the honest clock, DESIGN.md §3); ``--stale-clock`` replays
with the historical clocked-once-at-admission behaviour. ``--quick``
additionally times both clocks on the acceptance traces
(``table4_poisson``, ``serve_fleet``) and exits non-zero unless (a) the
re-clocked end-to-end wall time stays within 2x the stale baseline (the
incremental simulate path at work), (b) NewMapping still beats Blocked
on total message wait, and (c) the fleet accounting survives every run;
it also measures the disabled-recorder overhead ratio the baselines
gate at <= 3%.

``--trace`` records the run through the flight-recorder layer
(DESIGN.md §11): every scheduler decision, simulator call and remap
verdict lands in ``--trace-out`` (native ``repro-trace-v1`` JSON, plus
a ``.perfetto.json`` sibling loadable at https://ui.perfetto.dev), with
each strategy leg on its own Perfetto process. Dumps are byte-identical
across repeated seeded runs; host time is ``repro.obs.host_summary``'s
(or a JAX profiler trace's), never the dump's.

    PYTHONPATH=src python benchmarks/sched_bench.py \
        --scenario rack_oversub --trace --trace-out TRACE_sched.json

Results are emitted as JSON on stdout (and to --out when given), with
each strategy's metrics registry merged into its row.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time

from repro import obs
from repro.compile_cache import enable_compile_cache
from repro.obs import export as obs_export
from repro.sched import (AdmissionConfig, CellConfig, FleetScheduler,
                         RemapConfig, SchedulerConfig, get_trace,
                         trace_names)

DEFAULT_STRATEGIES = ("blocked", "cyclic", "drb", "new", "recursive_bisect")

# wall-clock grace for the --quick clock gate: tiny traces finish in
# tens of milliseconds where timer noise would dominate a pure ratio
_CLOCK_GRACE_S = 0.5


def run_trace(trace_name: str, strategies=DEFAULT_STRATEGIES, *,
              rate: float | None = None, n_arrivals: int | None = None,
              seed: int = 0, remap_interval: float | None = 5.0,
              util_threshold: float = 0.75, sim_backend: str = "auto",
              reclock: bool = True, admission_window: float = 0.0,
              cells: int | str = 1) -> dict:
    kwargs = {"seed": seed}
    if rate is not None:
        kwargs["rate"] = rate
    if n_arrivals is not None:
        kwargs["n_arrivals"] = n_arrivals
    results: dict[str, dict] = {}
    count_scale = None
    rec = obs.current()
    for strategy in strategies:
        if rec.enabled:
            # one Perfetto process per strategy leg
            rec.set_process(f"sched:{strategy}" if reclock
                            else f"sched:{strategy}:stale")
        spec = get_trace(trace_name, **kwargs)       # fresh graphs per run
        count_scale = spec.count_scale
        sched = FleetScheduler(
            spec.cluster, strategy,
            config=SchedulerConfig(
                remap=RemapConfig(interval=remap_interval,
                                  util_threshold=util_threshold),
                admission=AdmissionConfig(window=admission_window),
                cells=CellConfig(cells=cells),
                state_bytes_per_proc=spec.state_bytes_per_proc,
                count_scale=spec.count_scale,
                sim_backend=sim_backend,
                reclock=reclock))
        sched.submit_trace(spec.arrivals)
        t0 = time.perf_counter()
        stats = sched.run()
        wall = time.perf_counter() - t0
        sched.check_invariants()                     # fleet accounting intact
        results[strategy] = dict(stats.to_dict(), wall_time_s=round(wall, 4),
                                 metrics=sched.metrics.to_dict())

    def wait(s: str) -> float:
        return results[s]["total_msg_wait"]

    comparison = {}
    if "new" in results:
        for base in ("blocked", "cyclic", "drb"):
            if base in results and wait(base) > 0:
                comparison[f"new_vs_{base}_msg_wait_gain"] = round(
                    1.0 - wait("new") / wait(base), 4)
        comparison["new_beats_blocked_and_cyclic"] = bool(
            "blocked" in results and "cyclic" in results
            and wait("new") < wait("blocked") and wait("new") < wait("cyclic"))
    if "recursive_bisect" in results:
        others = [s for s in results if s != "recursive_bisect"]
        for base in others:
            if wait(base) > 0:
                comparison[f"rb_vs_{base}_msg_wait_gain"] = round(
                    1.0 - wait("recursive_bisect") / wait(base), 4)
        comparison["recursive_bisect_beats_all"] = bool(
            others and all(wait("recursive_bisect") < wait(s)
                           for s in others))
    return {
        "trace": trace_name,
        "params": {"seed": seed, "rate": rate, "n_arrivals": n_arrivals,
                   "remap_interval": remap_interval,
                   "util_threshold": util_threshold,
                   "count_scale": count_scale,
                   "sim_backend": sim_backend,
                   "reclock": reclock,
                   "admission_window": admission_window,
                   "cells": cells},
        "strategies": results,
        "comparison": comparison,
    }


def clock_comparison(trace_name: str, strategy: str = "new", *,
                     rate: float | None = None,
                     n_arrivals: int | None = None, seed: int = 0,
                     remap_interval: float | None = 5.0,
                     util_threshold: float = 0.75,
                     sim_backend: str = "auto",
                     reclock_row: dict | None = None) -> dict:
    """Same trace, stale clock vs re-clocking engine: wall time + makespan.

    The stale clock keys departures once at admission (one simulate per
    placement); the honest clock re-simulates after every fleet mutation.
    The incremental path (delta workload assembly + warm-start handle,
    DESIGN.md §8) is what keeps the honest clock's end-to-end wall time
    within 2x of the stale baseline despite ~2-3x the simulate calls.

    ``reclock_row`` reuses an already-measured strategy row (identical
    trace/params, reclock=True) for the re-clocked leg instead of
    replaying the deterministic run.
    """
    out: dict[str, dict] = {}
    for label, reclock in (("stale", False), ("reclock", True)):
        if reclock and reclock_row is not None:
            row = reclock_row
        else:
            rep = run_trace(trace_name, (strategy,), rate=rate,
                            n_arrivals=n_arrivals, seed=seed,
                            remap_interval=remap_interval,
                            util_threshold=util_threshold,
                            sim_backend=sim_backend, reclock=reclock)
            row = rep["strategies"][strategy]
        out[label] = {"wall_time_s": row["wall_time_s"],
                      "makespan": row["makespan"],
                      "total_msg_wait": row["total_msg_wait"],
                      "n_remap_commits": row["n_remap_commits"]}
    ratio = out["reclock"]["wall_time_s"] / max(out["stale"]["wall_time_s"],
                                                1e-9)
    return {
        "trace": trace_name,
        "strategy": strategy,
        "params": {"seed": seed, "rate": rate, "n_arrivals": n_arrivals,
                   "remap_interval": remap_interval,
                   "util_threshold": util_threshold,
                   "sim_backend": sim_backend},
        "stale": out["stale"],
        "reclock": out["reclock"],
        "wall_ratio": round(ratio, 3),
        "makespan_correction": round(
            out["reclock"]["makespan"] - out["stale"]["makespan"], 6),
    }


def cell_comparison(trace_name: str = "fleet64", strategy: str = "new", *,
                    n_arrivals: int = 24, seed: int = 0,
                    cells: int | str = "rack",
                    admission_window: float = 0.0,
                    sim_backend: str = "auto") -> dict:
    """Global scheduler vs cell-sharded fleet on a ≥64-node trace (§13).

    Shards the fleet into cells at ``cells`` granularity (a
    NetworkHierarchy level name or a node count divisor), each with its
    own tracker view and warm sim handle; re-clocks stay cell-local
    unless a job spans cells. Reports the wall-time speedup of the
    sharded run over the single-cell run — gated ``>= 1`` in
    ``baselines.json`` (``sched.cell_speedup``).
    """
    out: dict[str, dict] = {}
    for label, n_cells in (("global", 1), ("sharded", cells)):
        rep = run_trace(trace_name, (strategy,), n_arrivals=n_arrivals,
                        seed=seed, remap_interval=None,
                        sim_backend=sim_backend,
                        admission_window=admission_window, cells=n_cells)
        row = rep["strategies"][strategy]
        out[label] = {"wall_time_s": row["wall_time_s"],
                      "makespan": row["makespan"],
                      "total_msg_wait": row["total_msg_wait"],
                      "n_spanning_jobs": row["n_spanning_jobs"],
                      "n_cell_escalations": row["n_cell_escalations"]}
    speedup = out["global"]["wall_time_s"] / max(
        out["sharded"]["wall_time_s"], 1e-9)
    return {
        "trace": trace_name,
        "strategy": strategy,
        "params": {"seed": seed, "n_arrivals": n_arrivals, "cells": cells,
                   "admission_window": admission_window,
                   "sim_backend": sim_backend},
        "global": out["global"],
        "sharded": out["sharded"],
        "speedup": round(speedup, 3),
    }


def nested_cell_comparison(trace_name: str = "fleet1k",
                           strategy: str = "new", *, n_arrivals: int = 64,
                           rate: float = 16.0, seed: int = 0,
                           sim_backend: str = "auto") -> dict:
    """Global vs flat rack cells vs nested pod/rack cells on the 1k-node
    fleet (DESIGN.md §13/§14).

    The flat fabric escalates every rack-spanning job's re-clock to the
    whole fleet; the nested fabric stops one level up at the owning pod
    (512 cores instead of 8,192), so the rack-oversub mix's 48-proc jobs
    no longer couple the fleet. Reports the nested fabric's wall-time
    speedup over flat cells (``speedup_vs_flat``, gated ``>= 1`` as
    ``sched.nested_cell_speedup`` in ``baselines.json``) and over the
    unsharded scheduler (``speedup_vs_global``).
    """
    out: dict[str, dict] = {}
    for label, cells in (("global", 1), ("flat", "rack"),
                         ("nested", "pod/rack")):
        rep = run_trace(trace_name, (strategy,), n_arrivals=n_arrivals,
                        rate=rate, seed=seed, remap_interval=None,
                        sim_backend=sim_backend, cells=cells)
        row = rep["strategies"][strategy]
        out[label] = {"wall_time_s": row["wall_time_s"],
                      "makespan": row["makespan"],
                      "total_msg_wait": row["total_msg_wait"],
                      "n_spanning_jobs": row["n_spanning_jobs"],
                      "n_cell_escalations": row["n_cell_escalations"],
                      "n_cross_cell_migrations":
                          row["n_cross_cell_migrations"]}
    nested_w = max(out["nested"]["wall_time_s"], 1e-9)
    return {
        "trace": trace_name,
        "strategy": strategy,
        "params": {"seed": seed, "rate": rate, "n_arrivals": n_arrivals,
                   "sim_backend": sim_backend},
        "global": out["global"],
        "flat": out["flat"],
        "nested": out["nested"],
        "speedup_vs_flat": round(out["flat"]["wall_time_s"] / nested_w, 3),
        "speedup_vs_global": round(
            out["global"]["wall_time_s"] / nested_w, 3),
    }


def measure_obs_overhead(trace_name: str = "table4_poisson", *,
                         n_arrivals: int = 12, seed: int = 0,
                         repeats: int = 3) -> dict:
    """Disabled-recorder overhead ratio, gated in ``baselines.json``.

    The same quick run twice: once with the shared NULL no-op (nothing
    installed — the default for every un-instrumented program) and once
    with an explicit *disabled* ``Recorder`` passed into the scheduler.
    Both take the one-attribute-test fast path; the ratio guards against
    instrumentation creeping work in front of the ``enabled`` check.
    Best-of-``repeats`` walls to push timer noise below the 3% band.
    """
    def once(recorder) -> float:
        spec = get_trace(trace_name, seed=seed, n_arrivals=n_arrivals)
        sched = FleetScheduler(
            spec.cluster, "new",
            config=SchedulerConfig(
                remap=RemapConfig(interval=5.0),
                state_bytes_per_proc=spec.state_bytes_per_proc,
                count_scale=spec.count_scale),
            recorder=recorder)
        sched.submit_trace(spec.arrivals)
        t0 = time.perf_counter()
        sched.run()
        return time.perf_counter() - t0

    # measure with nothing installed even when the caller is tracing —
    # a recording base leg would make the ratio meaningless. Legs are
    # interleaved (min-of-N each) so both see the same background load.
    prev = obs.current()
    obs.install(None)
    try:
        once(None)                                   # warm caches
        disabled_rec = obs.Recorder(enabled=False)
        base = disabled = float("inf")
        for _ in range(repeats):
            base = min(base, once(None))             # the NULL fast path
            disabled = min(disabled, once(disabled_rec))
    finally:
        obs.install(prev if prev is not obs.NULL else None)
    return {"trace": trace_name, "repeats": repeats,
            "null_wall_s": round(base, 4),
            "disabled_wall_s": round(disabled, 4),
            "ratio": round(disabled / max(base, 1e-9), 3)}


def _smoke_failures(report: dict) -> list[str]:
    """CI assertions for --quick; returns failure messages."""
    fails = []
    for clk in report.get("clock", []):
        stale_w = clk["stale"]["wall_time_s"]
        re_w = clk["reclock"]["wall_time_s"]
        if re_w > max(2.0 * stale_w, stale_w + _CLOCK_GRACE_S):
            fails.append(
                f"{clk['trace']}: re-clocked wall time {re_w:.3f}s exceeds "
                f"2x the stale baseline {stale_w:.3f}s "
                f"(ratio {clk['wall_ratio']:.2f})")
    comparison = report.get("comparison", {})
    gain = comparison.get("new_vs_blocked_msg_wait_gain")
    if gain is not None and gain <= 0:
        fails.append(f"NewMapping no longer beats Blocked on msg wait "
                     f"(gain {gain})")
    nest = report.get("nested_cells")
    if nest and nest["speedup_vs_flat"] < 1.0:
        fails.append(
            f"nested pod/rack cells slower than flat rack cells on "
            f"{nest['trace']} ({nest['speedup_vs_flat']}x)")
    return fails


def _print_table(report: dict) -> None:
    rows = report["strategies"]
    print(f"# trace={report['trace']}  "
          f"params={report['params']}", file=sys.stderr)
    hdr = (f"{'strategy':10s} {'makespan(s)':>12s} {'queue-wait(s)':>14s} "
           f"{'msg-wait(s)':>14s} {'nic-p99':>8s} {'remaps':>7s} "
           f"{'wall(s)':>8s}")
    print(hdr, file=sys.stderr)
    for name, s in rows.items():
        print(f"{name:10s} {s['makespan']:12.2f} {s['total_queue_wait']:14.2f} "
              f"{s['total_msg_wait']:14.1f} {s['nic_p99_util']:8.3f} "
              f"{s['n_remap_commits']:3d}/{s['n_remap_rejects']:<3d} "
              f"{s['wall_time_s']:8.2f}",
              file=sys.stderr)
    for k, v in report["comparison"].items():
        print(f"  {k}: {v}", file=sys.stderr)
    for clk in report.get("clock", []):
        print(f"  clock[{clk['trace']}]: stale {clk['stale']['wall_time_s']}s"
              f" -> reclock {clk['reclock']['wall_time_s']}s"
              f" (ratio {clk['wall_ratio']}), makespan correction "
              f"{clk['makespan_correction']:+.3f}s", file=sys.stderr)
    cell = report.get("cells")
    if cell:
        print(f"  cells[{cell['trace']}]: global "
              f"{cell['global']['wall_time_s']}s -> sharded "
              f"{cell['sharded']['wall_time_s']}s "
              f"(speedup {cell['speedup']}x, "
              f"{cell['sharded']['n_spanning_jobs']} spanning, "
              f"{cell['sharded']['n_cell_escalations']} escalations)",
              file=sys.stderr)
    nest = report.get("nested_cells")
    if nest:
        print(f"  nested_cells[{nest['trace']}]: global "
              f"{nest['global']['wall_time_s']}s / flat "
              f"{nest['flat']['wall_time_s']}s -> nested "
              f"{nest['nested']['wall_time_s']}s "
              f"({nest['speedup_vs_flat']}x vs flat, "
              f"{nest['speedup_vs_global']}x vs global; "
              f"{nest['nested']['n_cell_escalations']} pod escalations vs "
              f"{nest['flat']['n_cell_escalations']} fleet escalations)",
              file=sys.stderr)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--scenario", default="table4_poisson",
                    choices=trace_names(), help="named arrival trace")
    ap.add_argument("--trace", action="store_true",
                    help="record a structured flight-recorder trace of the "
                         "run (repro.obs, DESIGN.md §11)")
    ap.add_argument("--trace-out", default="TRACE_sched.json",
                    help="native trace output path (a .perfetto.json "
                         "sibling is written next to it)")
    ap.add_argument("--strategies", nargs="+", default=list(DEFAULT_STRATEGIES))
    ap.add_argument("--rate", type=float, default=None,
                    help="Poisson arrival rate, jobs/s (trace default if unset)")
    ap.add_argument("--arrivals", type=int, default=24,
                    help="number of job arrivals in the trace")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--remap-interval", type=float, default=5.0,
                    help="seconds between contention-aware remap passes")
    ap.add_argument("--no-remap", action="store_true",
                    help="disable the periodic remap pass")
    ap.add_argument("--util-threshold", type=float, default=0.75)
    ap.add_argument("--sim-backend", default="auto",
                    help="simulator backend: auto|loop|segmented|jax|pallas")
    ap.add_argument("--stale-clock", action="store_true",
                    help="clock departures once at admission (the historical "
                         "baseline) instead of re-clocking on every mutation")
    ap.add_argument("--clock-compare", action="store_true",
                    help="also time stale vs re-clocked runs on this trace")
    ap.add_argument("--admission-window", type=float, default=0.0,
                    help="joint batched admission window in seconds "
                         "(0 = sequential FIFO, DESIGN.md §13)")
    ap.add_argument("--cells", default="1",
                    help="shard the fleet into cells: a node-count divisor "
                         "(e.g. 4) or a hierarchy level name (e.g. rack)")
    ap.add_argument("--cells-compare", action="store_true",
                    help="also time global vs cell-sharded runs on the "
                         "fleet64 trace (quick always does)")
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke: short trace + clock gate on the "
                         "acceptance traces, hard assertions")
    ap.add_argument("--out", default=None, help="also write JSON here")
    args = ap.parse_args(argv)
    enable_compile_cache()

    n_arrivals = 12 if args.quick else args.arrivals
    strategies = (("blocked", "cyclic", "new") if args.quick
                  else tuple(args.strategies))
    remap_interval = None if args.no_remap else args.remap_interval
    cells: int | str = int(args.cells) if str(args.cells).isdigit() \
        else args.cells

    # disabled-recorder overhead first, before any recorder is installed
    obs_overhead = measure_obs_overhead(seed=args.seed) if args.quick \
        else None

    recorder = obs.Recorder() if args.trace else obs.from_env()
    ctx = (obs.recording(recorder) if recorder is not None
           else contextlib.nullcontext())
    with ctx:
        report = run_trace(
            args.scenario, strategies,
            rate=args.rate, n_arrivals=n_arrivals, seed=args.seed,
            remap_interval=remap_interval,
            util_threshold=args.util_threshold, sim_backend=args.sim_backend,
            reclock=not args.stale_clock,
            admission_window=args.admission_window, cells=cells)
        if args.quick or args.cells_compare:
            # quick gates the canonical rack sharding; --cells-compare
            # honours the sweep flags (window + cell granularity)
            report["cells"] = cell_comparison(
                n_arrivals=24, seed=args.seed, sim_backend=args.sim_backend,
                **({} if args.quick else
                   {"cells": cells if cells != 1 else "rack",
                    "admission_window": args.admission_window}))
            # 1k-node fleet: nested pod/rack cells vs flat vs global —
            # quick trims the trace (the full-scale row is
            # `--scenario fleet1k --cells pod/rack --arrivals 2048`)
            report["nested_cells"] = nested_cell_comparison(
                n_arrivals=64 if args.quick else args.arrivals,
                seed=args.seed, sim_backend=args.sim_backend)
        if args.quick or args.clock_compare:
            # quick gates the fixed acceptance traces at their default
            # rates; --clock-compare mirrors exactly the run the user
            # asked for
            clock_traces = (("table4_poisson", None, 12),
                            ("serve_fleet", None, None)) \
                if args.quick else ((args.scenario, args.rate, n_arrivals),)
            report["clock"] = []
            for t, r, n in clock_traces:
                # the main table already ran this exact re-clocked config —
                # reuse its row instead of replaying the deterministic run
                same = (t == args.scenario and r == args.rate
                        and n == n_arrivals
                        and "new" in report["strategies"]
                        and not args.stale_clock
                        and args.admission_window == 0.0 and cells == 1)
                report["clock"].append(clock_comparison(
                    t, rate=r, n_arrivals=n, seed=args.seed,
                    remap_interval=remap_interval,
                    util_threshold=args.util_threshold,
                    sim_backend=args.sim_backend,
                    reclock_row=report["strategies"]["new"] if same
                    else None))
    if obs_overhead is not None:
        report["obs_overhead"] = obs_overhead
    if recorder is not None:
        doc = recorder.dump(
            extra_metrics={f"sched/{s}": row["metrics"]
                           for s, row in report["strategies"].items()})
        with open(args.trace_out, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
        perfetto_out = args.trace_out.replace(".json", "") + ".perfetto.json"
        with open(perfetto_out, "w") as f:
            json.dump(obs_export.to_chrome(doc), f, indent=1, sort_keys=True)
        print(f"trace: {recorder.n_events()} events -> {args.trace_out} "
              f"(+ {perfetto_out})", file=sys.stderr)
    _print_table(report)
    text = json.dumps(report, indent=1, sort_keys=True)
    print(text)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text)
    if args.quick:
        fails = _smoke_failures(report)
        for m in fails:
            print(f"SMOKE FAIL: {m}", file=sys.stderr)
        if fails:
            raise SystemExit(1)


if __name__ == "__main__":
    main()
