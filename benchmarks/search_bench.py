"""Batched placement search shoot-out vs the one-shot strategies.

For each scenario (the paper's Table-4 mix, the oversubscribed-rack mix,
the TPU serving-fleet mix) the bench:

* places the job set with every one-shot strategy and scores it with the
  queueing simulator at the search's own objective resolution,
* runs ``search:new`` over an evaluation-budget sweep (objective vs
  budget curve) plus one ``anneal`` run, recording wall time and the
  exact number of placements scored,
* times the same fixed-budget search on each available simulator
  backend (segmented numpy vs jax; one batched scan per population on
  jax), and
* replays dynamic arrival traces through ``FleetScheduler`` with the
  search at admission time and the budgeted population remap pass.

    PYTHONPATH=src python benchmarks/search_bench.py --out BENCH_search.json
    PYTHONPATH=src python benchmarks/search_bench.py --quick  # CI smoke gate

``--quick`` shrinks budgets/traces and exits non-zero unless (a)
``search:new`` strictly beats its ``new`` seed on the rack_oversub
scenario (oversubscription 4), (b) it at least matches the best one-shot
strategy on the Table-4 scenario, (c) every recorded search stayed
within 500 simulator evaluations, and (d) joint batched admission
(``search:new`` with the §13 admission window) does not lose to plain
``new`` on the table4_poisson dynamic trace — the fix for the
admission-in-isolation regression that row used to document. Results
are emitted as JSON on stdout (and to ``--out`` when given).
"""

from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time

from repro import obs
from repro.compile_cache import enable_compile_cache
from repro.core.graphs import ClusterTopology
from repro.core.mapping import ONE_SHOT_STRATEGIES, STRATEGIES, make_search_strategy
from repro.core.meshplan import tpu_topology
from repro.core.workloads import rack_oversub_mix, synt_workload_3
from repro.sched import FleetScheduler, SchedulerConfig, get_trace
from repro.sched.traces import rack_oversub_cluster, serve_fleet_mix
from repro.search import auto_objective_scale, objective_of, search_placement

EVAL_CAP = 500  # acceptance: every search stays within this many evaluations


def _scenarios() -> dict:
    return {
        "table4": (synt_workload_3, ClusterTopology),
        "rack_oversub": (rack_oversub_mix, lambda: rack_oversub_cluster(oversub=4.0)),
        "serve_fleet": (serve_fleet_mix, lambda: tpu_topology(n_pods=2)),
    }


def _jax_available() -> bool:
    try:
        import jax  # noqa: F401

        return True
    except Exception:
        return False


def run_static(
    name: str,
    jobs_fn,
    cluster_fn,
    budgets: list[int],
    rng_seed: int = 0,
    backend: str = "auto",
) -> dict:
    """One-shot strategies vs search/anneal on a static job batch."""
    jobs = jobs_fn()
    cluster = cluster_fn()
    scale = auto_objective_scale(jobs)
    one_shot: dict[str, dict] = {}
    for strat in ONE_SHOT_STRATEGIES:
        t0 = time.perf_counter()
        placement = STRATEGIES[strat](jobs, cluster)
        obj = objective_of(
            jobs, placement, cluster, objective_scale=scale, backend=backend
        )
        one_shot[strat] = {
            "objective": obj,
            "wall_s": round(time.perf_counter() - t0, 4),
        }
    curve = []
    for budget in budgets:
        t0 = time.perf_counter()
        res = search_placement(
            jobs,
            cluster,
            seed="new",
            budget=budget,
            rng_seed=rng_seed,
            objective_scale=scale,
            backend=backend,
        )
        curve.append(
            {
                "budget": budget,
                "evaluations": res.evaluations,
                "accepted": res.accepted,
                "objective": res.objective,
                "gain_vs_seed": round(res.gain_vs_seed, 4),
                "wall_s": round(time.perf_counter() - t0, 4),
            }
        )
    t0 = time.perf_counter()
    ann = search_placement(
        jobs,
        cluster,
        seed="new",
        budget=budgets[-1],
        anneal=True,
        rng_seed=rng_seed,
        objective_scale=scale,
        backend=backend,
    )
    search_obj = curve[-1]["objective"]
    best_one_shot = min(v["objective"] for v in one_shot.values())
    return {
        "objective_scale": scale,
        "n_jobs": len(jobs),
        "n_procs": sum(j.n_procs for j in jobs),
        "one_shot": one_shot,
        "search": {"seed": "new", "budget_curve": curve, "objective": search_obj},
        "anneal": {
            "budget": budgets[-1],
            "evaluations": ann.evaluations,
            "objective": ann.objective,
            "gain_vs_seed": round(ann.gain_vs_seed, 4),
            "wall_s": round(time.perf_counter() - t0, 4),
        },
        "win_loss": {
            "wins": sorted(
                s for s, v in one_shot.items() if search_obj < v["objective"]
            ),
            "ties": sorted(
                s for s, v in one_shot.items() if search_obj == v["objective"]
            ),
            "losses": sorted(
                s for s, v in one_shot.items() if search_obj > v["objective"]
            ),
        },
        "beats_seed": search_obj < one_shot["new"]["objective"],
        "matches_best_one_shot": search_obj <= best_one_shot,
        "max_evaluations": max(
            [row["evaluations"] for row in curve] + [ann.evaluations]
        ),
    }


def run_backends(budget: int, rng_seed: int = 0) -> dict:
    """Same search, same seed, per backend: wall time + objective parity."""
    backends = ["segmented"] + (["jax"] if _jax_available() else [])
    out: dict[str, dict] = {}
    for backend in backends:
        jobs = rack_oversub_mix()
        cluster = rack_oversub_cluster(oversub=4.0)
        t0 = time.perf_counter()
        res = search_placement(
            jobs,
            cluster,
            seed="new",
            budget=budget,
            rng_seed=rng_seed,
            backend=backend,
        )
        out[backend] = {
            "wall_s": round(time.perf_counter() - t0, 4),
            "objective": res.objective,
            "evaluations": res.evaluations,
            "trajectory_len": len(res.trajectory),
        }
    objs = {v["objective"] for v in out.values()}
    out["agree"] = len(objs) == 1
    return out


ADMISSION_WINDOW = 0.5  # seconds; the §13 joint-admission batching window


def run_dynamic(
    trace_name: str,
    n_arrivals: int,
    admission_budget: int,
    remap_budget: int,
    seed: int = 0,
) -> dict:
    """FleetScheduler replay: one-shot ``new`` vs the search strategies.

    The admission rows (``new``, ``search:new``, ``search:new:isolated``)
    run without the background remap pass: at this trace scale a remap
    tick racing a departure swings total wait by double digits, which
    swamps the admission-policy signal being compared (the remap pass
    keeps its own ``new+remap_search`` row). ``search:new`` routes
    admission-time search through the joint batched path (DESIGN.md
    §13) — every arrival window is placed as one batch scored against
    the full live set. ``search:new:isolated`` pins the old behaviour,
    each arrival search-placed in isolation, preserving the documented
    admission-in-isolation regression for comparison.
    """
    rows: dict[str, dict] = {}
    variants = {
        "new": {"strategy": "new"},
        "search:new": {
            "strategy": make_search_strategy("new", budget=admission_budget),
            "admission_window": ADMISSION_WINDOW,
        },
        "search:new:isolated": {
            "strategy": make_search_strategy("new", budget=admission_budget),
        },
        "new+remap_search": {
            "strategy": "new",
            "remap_interval": 5.0,
            "remap_budget": remap_budget,
        },
    }
    for label, cfg in variants.items():
        cfg = dict(cfg)
        spec = get_trace(trace_name, seed=seed, n_arrivals=n_arrivals)
        sched = FleetScheduler(
            spec.cluster,
            cfg.pop("strategy"),
            config=SchedulerConfig.from_legacy(
                state_bytes_per_proc=spec.state_bytes_per_proc,
                count_scale=spec.count_scale,
                **cfg,
            ),
        )
        sched.submit_trace(spec.arrivals)
        t0 = time.perf_counter()
        stats = sched.run()
        sched.check_invariants()
        rows[label] = {
            "total_msg_wait": stats.total_msg_wait,
            "makespan": stats.makespan,
            "n_remap_commits": stats.n_remap_commits,
            "n_joint_batches": stats.n_joint_batches,
            "n_joint_admitted": stats.n_joint_admitted,
            "hol_blocked_core_s": stats.hol_blocked_core_s,
            "wall_s": round(time.perf_counter() - t0, 4),
        }
    base = rows["new"]["total_msg_wait"]
    for label, row in rows.items():
        row["msg_wait_gain_vs_new"] = (
            round(1.0 - row["total_msg_wait"] / base, 4) if base > 0 else 0.0
        )
    return {"trace": trace_name, "n_arrivals": n_arrivals, "strategies": rows}


def gate_failures(report: dict) -> list[str]:
    """CI assertions (ISSUE 5 acceptance) — returns failure messages."""
    fails = []
    rack = report["static"].get("rack_oversub")
    if rack and not rack["beats_seed"]:
        fails.append(
            "search:new does not beat its new seed on rack_oversub "
            f"({rack['search']['objective']} vs {rack['one_shot']['new']['objective']})"
        )
    table4 = report["static"].get("table4")
    if table4 and not table4["matches_best_one_shot"]:
        fails.append(
            "search:new does not match the best one-shot strategy on table4 "
            f"({table4['search']['objective']} vs best "
            f"{min(v['objective'] for v in table4['one_shot'].values())})"
        )
    for name, row in report["static"].items():
        if row["max_evaluations"] > EVAL_CAP:
            fails.append(
                f"{name}: search used {row['max_evaluations']} evaluations "
                f"(cap {EVAL_CAP})"
            )
    for dyn in report.get("dynamic", []):
        if dyn["trace"] != "table4_poisson":
            continue
        gain = dyn["strategies"]["search:new"]["msg_wait_gain_vs_new"]
        if gain < 0.0:
            fails.append(
                "joint batched admission loses to plain new on "
                f"table4_poisson (msg_wait_gain_vs_new={gain})"
            )
    backends = report.get("backends")
    if backends and not backends.get("agree", True):
        fails.append(
            "search objective disagrees across simulator backends: "
            + ", ".join(
                f"{k}={v['objective']}"
                for k, v in backends.items()
                if isinstance(v, dict)
            )
        )
    return fails


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "--scenarios",
        nargs="+",
        default=None,
        choices=sorted(_scenarios()),
        help="static scenarios to run (default: all; quick: table4+rack)",
    )
    ap.add_argument(
        "--budgets",
        nargs="+",
        type=int,
        default=None,
        help="evaluation-budget sweep (default 60 180 480; quick 48 120)",
    )
    ap.add_argument("--rng-seed", type=int, default=0)
    ap.add_argument("--seed", type=int, default=0, help="trace seed (dynamic part)")
    ap.add_argument("--backend", default="auto")
    ap.add_argument("--skip-dynamic", action="store_true")
    ap.add_argument(
        "--quick",
        action="store_true",
        help="CI smoke: small budgets/traces, hard assertions",
    )
    ap.add_argument("--trace", action="store_true",
                    help="record a flight-recorder trace (repro.obs) of "
                         "every search run to --trace-out")
    ap.add_argument("--trace-out", default="TRACE_search.json")
    ap.add_argument("--out", default=None, help="also write JSON here")
    args = ap.parse_args(argv)
    enable_compile_cache()

    recorder = obs.Recorder() if args.trace else obs.from_env()
    _rec_ctx = (obs.recording(recorder) if recorder is not None
                else contextlib.nullcontext())
    _rec_ctx.__enter__()
    budgets = args.budgets or ([48, 120] if args.quick else [60, 180, 480])
    scen_names = args.scenarios or (
        ["table4", "rack_oversub"] if args.quick else sorted(_scenarios())
    )
    report: dict = {
        "params": {
            "budgets": budgets,
            "rng_seed": args.rng_seed,
            "seed": args.seed,
            "backend": args.backend,
            "quick": args.quick,
        },
        "static": {},
    }
    for name in scen_names:
        jobs_fn, cluster_fn = _scenarios()[name]
        row = run_static(
            name,
            jobs_fn,
            cluster_fn,
            budgets,
            rng_seed=args.rng_seed,
            backend=args.backend,
        )
        report["static"][name] = row
        print(
            f"{name}: search={row['search']['objective']:.1f}s "
            f"(seed new={row['one_shot']['new']['objective']:.1f}s, "
            f"best={min(v['objective'] for v in row['one_shot'].values()):.1f}s) "
            f"wins={row['win_loss']['wins']}",
            file=sys.stderr,
        )

    report["backends"] = run_backends(budgets[0], rng_seed=args.rng_seed)
    if not args.skip_dynamic:
        n_arrivals = 8 if args.quick else 16
        admission_budget = 64 if args.quick else 192
        remap_budget = 64 if args.quick else 160
        report["dynamic"] = [
            run_dynamic(
                trace,
                n_arrivals,
                admission_budget,
                remap_budget,
                seed=args.seed,
            )
            for trace in ("rack_oversub", "table4_poisson")
        ]
        for dyn in report["dynamic"]:
            msg = "  ".join(
                f"{s}={r['total_msg_wait']:.0f}s" for s, r in dyn["strategies"].items()
            )
            print(f"dynamic {dyn['trace']}: {msg}", file=sys.stderr)

    _rec_ctx.__exit__(None, None, None)
    if recorder is not None:
        with open(args.trace_out, "w") as f:
            f.write(recorder.dump_json())
        print(f"trace: {recorder.n_events()} events -> {args.trace_out}",
              file=sys.stderr)

    fails = gate_failures(report)
    report["gate"] = {"ok": not fails, "failures": fails, "eval_cap": EVAL_CAP}
    text = json.dumps(report, indent=1, sort_keys=True)
    print(text)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text)
    if args.quick:
        for m in fails:
            print(f"SMOKE FAIL: {m}", file=sys.stderr)
        if fails:
            raise SystemExit(1)


if __name__ == "__main__":
    main()
