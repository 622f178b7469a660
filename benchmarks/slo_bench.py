"""Serving-fleet SLO benchmark: autoscale closed loop vs static replicas.

Replays the bursty ``serve_slo`` scenario (DESIGN.md §15 — diurnal swell
plus a 3x mid-run spike on the hot model, on the 4x-oversubscribed-rack
cluster) through ``repro.sched.FleetScheduler`` twice:

* ``static``    — the autoscale engine observes traffic and accounts
                  SLO violations but takes no structural actions
                  (``AutoscaleConfig(actions=False, routing="uniform")``):
                  the initial replica set serves the whole horizon.
* ``autoscale`` — the full closed loop: add-replica / drop-replica
                  actions priced in wait-rate currency and committed only
                  when a warm ``simulate_batch`` trial confirms reduced
                  projected violation-seconds, plus placement-aware
                  (``"capacity"``) routing-weight refreshes.

Both legs score on **SLO violation-seconds**: the integral of wall-clock
time during which any model's projected p99 request latency exceeds its
target. ``check_invariants()`` runs after the full event stream, so a
scale action that corrupts the free-core tracker fails loudly.

    PYTHONPATH=src python benchmarks/slo_bench.py --out BENCH_slo.json
    PYTHONPATH=src python benchmarks/slo_bench.py --quick   # CI gate

Hard gates (``--quick`` and full runs both enforce them):

* the autoscale leg accrues strictly fewer violation-seconds than the
  static leg (the headline ``slo.autoscale_beats_static`` baseline);
* the autoscale leg commits at least one scale-up — otherwise the
  comparison is vacuous (the spike never stressed the fleet);
* zero invariant violations in either leg.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

from repro.compile_cache import enable_compile_cache
from repro.sched import (AutoscaleConfig, FleetScheduler, RemapConfig,
                         SchedulerConfig, get_trace)

LEGS = (
    ("static", False, "uniform"),
    ("autoscale", True, "capacity"),
)


def run_leg(actions: bool, routing: str, *, seed: int = 0,
            horizon: float = 240.0, epoch_dt: float = 4.0,
            max_replicas: int = 5, lookahead_s: float = 30.0,
            sim_backend: str = "auto") -> dict:
    """One full-horizon serving run; returns the SLO scorecard."""
    spec = get_trace("serve_slo", seed=seed, horizon=horizon,
                     epoch_dt=epoch_dt)
    sched = FleetScheduler(spec.cluster, "new", config=SchedulerConfig(
        remap=RemapConfig(interval=None),      # isolate the serving loop
        autoscale=AutoscaleConfig(enabled=True, actions=actions,
                                  routing=routing, slos=spec.slos,
                                  max_replicas=max_replicas,
                                  lookahead_s=lookahead_s),
        state_bytes_per_proc=spec.state_bytes_per_proc,
        count_scale=spec.count_scale,
        sim_backend=sim_backend))
    for g in spec.replicas:
        sched.submit(g, at=0.0, resident=True)
    sched.submit_traffic(spec.stream)
    t0 = time.perf_counter()
    stats = sched.run()
    wall = time.perf_counter() - t0
    sched.check_invariants()
    return {
        "slo_violation_s": stats.slo_violation_s,
        "slo_violation_by_model": stats.slo_violation_by_model,
        "n_scale_ups": stats.n_scale_ups,
        "n_scale_downs": stats.n_scale_downs,
        "n_autoscale_rejects": stats.n_autoscale_rejects,
        "n_routing_shifts": stats.n_routing_shifts,
        "n_live_end": len(sched.live),
        "makespan": stats.makespan,
        "total_msg_wait": stats.total_msg_wait,
        "wall_time_s": round(wall, 4),
    }


def run_report(*, seed: int = 0, horizon: float = 240.0,
               epoch_dt: float = 4.0, max_replicas: int = 5,
               sim_backend: str = "auto") -> dict:
    report = {
        "trace": "serve_slo",
        "params": {"seed": seed, "horizon": horizon, "epoch_dt": epoch_dt,
                   "max_replicas": max_replicas,
                   "sim_backend": sim_backend},
    }
    for name, actions, routing in LEGS:
        report[name] = run_leg(actions, routing, seed=seed, horizon=horizon,
                               epoch_dt=epoch_dt, max_replicas=max_replicas,
                               sim_backend=sim_backend)
    static_v = report["static"]["slo_violation_s"]
    auto_v = report["autoscale"]["slo_violation_s"]
    report["comparison"] = {
        "autoscale_beats_static": bool(auto_v < static_v),
        "violation_s_saved": round(static_v - auto_v, 4),
        "violation_reduction": (round(1.0 - auto_v / static_v, 4)
                                if static_v > 0 else None),
    }
    return report


def _smoke_failures(report: dict) -> list[str]:
    """CI assertions; returns failure messages (empty = pass)."""
    fails = []
    if not report["comparison"]["autoscale_beats_static"]:
        fails.append(
            "autoscale no longer beats static replicas on violation-seconds "
            f"(static={report['static']['slo_violation_s']:.1f}s, "
            f"autoscale={report['autoscale']['slo_violation_s']:.1f}s)")
    if report["autoscale"]["n_scale_ups"] < 1:
        fails.append("autoscale leg committed no scale-ups — the spike "
                     "never stressed the fleet; the comparison is vacuous")
    if report["static"]["n_scale_ups"] or report["static"]["n_scale_downs"]:
        fails.append("static leg took structural actions despite "
                     "actions=False")
    return fails


def _print_table(report: dict) -> None:
    print(f"# trace={report['trace']}  horizon={report['params']['horizon']:g}"
          f"  epoch_dt={report['params']['epoch_dt']:g}", file=sys.stderr)
    hdr = (f"{'leg':10s} {'viol(s)':>8s} {'ups':>4s} {'downs':>5s} "
           f"{'rejects':>7s} {'shifts':>6s} {'live@end':>8s} {'wall':>7s}")
    print(hdr, file=sys.stderr)
    for name, _, _ in LEGS:
        s = report[name]
        print(f"{name:10s} {s['slo_violation_s']:8.1f} {s['n_scale_ups']:4d} "
              f"{s['n_scale_downs']:5d} {s['n_autoscale_rejects']:7d} "
              f"{s['n_routing_shifts']:6d} {s['n_live_end']:8d} "
              f"{s['wall_time_s']:7.2f}", file=sys.stderr)
    for k, v in report["comparison"].items():
        print(f"  {k}: {v}", file=sys.stderr)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--horizon", type=float, default=240.0)
    ap.add_argument("--epoch-dt", type=float, default=4.0)
    ap.add_argument("--max-replicas", type=int, default=5)
    ap.add_argument("--sim-backend", default="auto")
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke: half horizon, hard assertions")
    ap.add_argument("--out", default=None, help="also write JSON here")
    args = ap.parse_args(argv)
    enable_compile_cache()

    horizon = 120.0 if args.quick else args.horizon
    report = run_report(seed=args.seed, horizon=horizon,
                        epoch_dt=args.epoch_dt,
                        max_replicas=args.max_replicas,
                        sim_backend=args.sim_backend)
    _print_table(report)
    text = json.dumps(report, indent=1, sort_keys=True)
    print(text)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text)
    fails = _smoke_failures(report)
    for m in fails:
        print(f"SMOKE FAIL: {m}", file=sys.stderr)
    if fails:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
