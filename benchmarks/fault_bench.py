"""Failure-aware fleet shoot-out: recovery policies under injected faults.

Replays the committed reference fault scenario (seeded node failures, a
rack blast, two maintenance drains pinned onto busy nodes — DESIGN.md
§12) through ``repro.sched.FleetScheduler`` once per recovery-policy
combination and scores each run on goodput (useful core-seconds /
allocated core-seconds), lost work, restarts/shrinks/evacuations and
MTTR. ``check_invariants()`` runs after **every** event, so a policy
that corrupts the free-core tracker or leaves a job on a dead node
fails loudly rather than skewing the numbers.

    PYTHONPATH=src python benchmarks/fault_bench.py
    PYTHONPATH=src python benchmarks/fault_bench.py --quick   # CI gate
    PYTHONPATH=src python benchmarks/fault_bench.py \
        --scenario table4_poisson --out BENCH_fault.json

Policy combinations measured:

* ``requeue_kill``      — checkpoint-restart recovery; drains hard-kill
                          whatever is still resident at the deadline.
* ``elastic_kill``      — elastic-shrink recovery (survivors re-meshed
                          via ElasticReMesher); same hard-kill drains.
* ``requeue_proactive`` — checkpoint-restart recovery; drains evacuate
                          resident jobs with the budgeted placement
                          search before the deadline.

The full run adds a failure-rate sweep (MTBF scaled from gentle to
brutal) so the policy ranking is visible as a function of fault
pressure, not just at one operating point.

Hard gates (``--quick`` and full runs both enforce them):

* zero invariant violations across every event of every run;
* every policy drains its queue — no job is lost or stuck pending;
* the kill-mode drains actually kill resident jobs (``dkills > 0``) —
  otherwise the proactive comparison is vacuous;
* proactive drains achieve strictly higher goodput than hard kills;
* an **empty** fault trace reproduces the no-fault run bit-identically
  (per-job departures and makespan) — the failure engine is pay-for-
  what-you-use.

Results are emitted as JSON on stdout (and to --out when given).
"""
from __future__ import annotations

import argparse
import json
import sys
import time

from repro.compile_cache import enable_compile_cache
from repro.sched import (FleetScheduler, RecoveryConfig, SchedulerConfig,
                         fault_trace, get_trace, reference_fault_trace,
                         trace_names)

POLICIES = (
    ("requeue_kill", "requeue", "kill"),
    ("elastic_kill", "elastic", "kill"),
    ("requeue_proactive", "requeue", "proactive"),
)


def run_policy(trace_name: str, failure_policy: str, drain_policy: str, *,
               faults=None, seed: int = 0, strategy: str = "new",
               check_every_event: bool = True) -> dict:
    """One scheduler run under one recovery-policy combination."""
    spec = get_trace(trace_name, seed=seed)
    sched = FleetScheduler(
        spec.cluster, strategy,
        config=SchedulerConfig(
            recovery=RecoveryConfig(failure_policy=failure_policy,
                                    drain_policy=drain_policy),
            count_scale=spec.count_scale,
            state_bytes_per_proc=spec.state_bytes_per_proc))
    sched.submit_trace(spec.arrivals)
    if faults is not None:
        sched.submit_faults(faults)
    violations: list[str] = []
    t0 = time.perf_counter()
    while sched.step():
        if check_every_event:
            try:
                sched.check_invariants()
            except Exception as exc:          # noqa: BLE001 — gate, report all
                violations.append(f"t={sched.now:.3f}: {exc}")
    wall = time.perf_counter() - t0
    if not check_every_event:
        sched.check_invariants()
    stats = sched.stats()
    return dict(
        stats.to_dict(),
        wall_time_s=round(wall, 4),
        invariant_violations=violations,
        pending_left=len(sched.pending),
        departures={jid: job.departure for jid, job in sched.done.items()},
    )


def run_reference(trace_name: str, seed: int = 0) -> dict:
    """The three policy combinations on the committed reference trace."""
    spec = get_trace(trace_name, seed=seed)
    faults = reference_fault_trace(spec.cluster)
    rows = {}
    for label, failure, drain in POLICIES:
        rows[label] = run_policy(trace_name, failure, drain,
                                 faults=faults, seed=seed)
    return {
        "n_fault_events": len(faults),
        "policies": rows,
        "comparison": {
            "proactive_vs_kill_goodput_gain": round(
                rows["requeue_proactive"]["goodput"]
                - rows["requeue_kill"]["goodput"], 4),
            "drain_beats_kill": bool(
                rows["requeue_proactive"]["goodput"]
                > rows["requeue_kill"]["goodput"]),
        },
    }


def run_sweep(trace_name: str, seed: int = 0,
              mtbf_scales=(8.0, 4.0, 2.0, 1.0)) -> list[dict]:
    """Goodput per policy as failure pressure rises (MTBF shrinks)."""
    spec = get_trace(trace_name, seed=seed)
    horizon = 45.0
    out = []
    for scale in mtbf_scales:
        faults = fault_trace(spec.cluster, horizon=horizon,
                             node_mtbf=horizon * scale,
                             node_mttr=horizon / 5,
                             rack_mtbf=horizon * scale, rack_size=4,
                             seed=seed + 99)
        row = {"mtbf_scale": scale, "n_fault_events": len(faults),
               "policies": {}}
        for label, failure, drain in POLICIES:
            r = run_policy(trace_name, failure, drain, faults=faults,
                           seed=seed)
            row["policies"][label] = {
                "goodput": r["goodput"],
                "lost_work_s": r["lost_work_s"],
                "makespan": r["makespan"],
                "n_restarts": r["n_restarts"],
                "n_shrinks": r["n_shrinks"],
                "invariant_violations": r["invariant_violations"],
                "pending_left": r["pending_left"],
            }
        out.append(row)
    return out


def run_nofault_parity(trace_name: str, seed: int = 0) -> dict:
    """Empty fault trace vs no fault engine at all: must be identical."""
    base = run_policy(trace_name, "requeue", "proactive", faults=None,
                      seed=seed)
    empty = run_policy(trace_name, "requeue", "proactive", faults=[],
                       seed=seed)
    identical = (base["departures"] == empty["departures"]
                 and base["makespan"] == empty["makespan"])
    return {
        "identical": bool(identical),
        "makespan": base["makespan"],
        "makespan_with_empty_faults": empty["makespan"],
    }


def _smoke_failures(report: dict) -> list[str]:
    """CI assertions; returns failure messages (empty = pass)."""
    fails = []
    ref = report["reference"]
    for label, row in ref["policies"].items():
        if row["invariant_violations"]:
            fails.append(f"{label}: {len(row['invariant_violations'])} "
                         f"invariant violations, first: "
                         f"{row['invariant_violations'][0]}")
        if row["pending_left"]:
            fails.append(f"{label}: {row['pending_left']} jobs stuck pending")
        if row["n_jobs"] != ref["policies"]["requeue_kill"]["n_jobs"]:
            fails.append(f"{label}: job count diverged")
    if ref["policies"]["requeue_kill"]["n_drain_kills"] <= 0:
        fails.append("reference trace drains killed nothing in kill mode — "
                     "the proactive comparison is vacuous")
    if not ref["comparison"]["drain_beats_kill"]:
        fails.append("proactive drain no longer beats hard kill on goodput "
                     f"(gain {ref['comparison']['proactive_vs_kill_goodput_gain']})")
    if not report["nofault_parity"]["identical"]:
        fails.append("empty fault trace perturbed the no-fault run "
                     "(departures or makespan changed)")
    for row in report.get("sweep", []):
        for label, r in row["policies"].items():
            if r["invariant_violations"]:
                fails.append(f"sweep mtbf_scale={row['mtbf_scale']} {label}: "
                             f"invariant violations")
            if r["pending_left"]:
                fails.append(f"sweep mtbf_scale={row['mtbf_scale']} {label}: "
                             f"jobs stuck pending")
    return fails


def _print_table(report: dict) -> None:
    ref = report["reference"]
    print(f"# trace={report['trace']}  "
          f"fault_events={ref['n_fault_events']}", file=sys.stderr)
    hdr = (f"{'policy':18s} {'makespan':>9s} {'goodput':>8s} {'lost(s)':>8s} "
           f"{'restart':>7s} {'shrink':>6s} {'evac':>5s} {'dkill':>5s} "
           f"{'mttr':>6s}")
    print(hdr, file=sys.stderr)
    for label, s in ref["policies"].items():
        print(f"{label:18s} {s['makespan']:9.2f} {s['goodput']:8.4f} "
              f"{s['lost_work_s']:8.2f} {s['n_restarts']:7d} "
              f"{s['n_shrinks']:6d} {s['n_evacuations']:5d} "
              f"{s['n_drain_kills']:5d} {s['mttr_mean']:6.2f}",
              file=sys.stderr)
    for k, v in ref["comparison"].items():
        print(f"  {k}: {v}", file=sys.stderr)
    print(f"  nofault_parity: {report['nofault_parity']['identical']}",
          file=sys.stderr)
    for row in report.get("sweep", []):
        cells = "  ".join(
            f"{label}={r['goodput']:.4f}"
            for label, r in row["policies"].items())
        print(f"  sweep mtbf x{row['mtbf_scale']:<4g} "
              f"({row['n_fault_events']:3d} events): {cells}",
              file=sys.stderr)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--scenario", default="table4_poisson",
                    choices=trace_names(), help="named arrival trace")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke: reference trace + gates, no MTBF sweep")
    ap.add_argument("--out", default=None, help="also write JSON here")
    args = ap.parse_args(argv)
    enable_compile_cache()

    report = {
        "trace": args.scenario,
        "params": {"seed": args.seed, "strategy": "new"},
        "reference": run_reference(args.scenario, seed=args.seed),
        "nofault_parity": run_nofault_parity(args.scenario, seed=args.seed),
    }
    if not args.quick:
        report["sweep"] = run_sweep(args.scenario, seed=args.seed)

    # departures are gate plumbing, not benchmark output — drop before dump
    for row in report["reference"]["policies"].values():
        row.pop("departures", None)

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
