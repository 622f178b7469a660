"""On-chip smoke test: the fleet scheduler's simulator on one TPU.

    python chip_smoke.py

One process, which holds the chip; it exits non-zero at once when JAX
reports no TPU. Two phases, each checked against a host reference on the
same inputs:

1. static — the live Table-4 snapshot on the paper's Table-1 cluster at
   the paper's full message counts (``count_scale=1.0``): ``simulate``
   and a K=4 ``simulate_batch`` on ``jax`` (f64, to 1e-9 relative) and
   then ``pallas`` (f32, to 2e-3) against the per-server ``loop``
   reference, with the Pallas call compiled, not interpreted; the error
   against numpy ``segmented`` is reported beside it.
2. scheduler — ``table4_poisson`` through ``FleetScheduler`` with remap
   passes every 5 s, on the device backend ``auto`` picks and then on
   ``segmented``: the same admits and remap commits, and makespan and
   total message wait within 1e-9 relative.

Earlier lines report each phase; first-call and warm-call times are
set-up and smoke timings, not metrics. The last line is the JSON verdict.
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT / "benchmarks")]

TRACE = "table4_poisson"
K = 4                                    # simulate_batch candidates
TOL = {"jax": 1e-9, "pallas": 2e-3}      # vs loop, relative
SCHED_TOL = 1e-9


class CompileLog:
    """Backend compile seconds and persistent-cache hits, from JAX's own
    monitoring events."""

    def __init__(self):
        import jax
        self.compile_s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def take(self) -> str:
        out = (f"backend_compile_s={self.compile_s:.3f} "
               f"cache_hits={self.cache_hits}")
        self.compile_s, self.cache_hits = 0.0, 0
        return out


def _rel(a: float, b: float) -> float:
    return abs(a - b) / abs(b) if b else abs(a)


def max_rel_err(res, ref) -> float:
    """Largest relative error of a SimResult over every metric it reports."""
    if res.n_messages != ref.n_messages:
        raise AssertionError(f"{res.n_messages} != {ref.n_messages} messages")
    errs = [_rel(res.total_wait, ref.total_wait),
            _rel(res.workload_finish, ref.workload_finish),
            _rel(res.total_job_finish, ref.total_job_finish),
            _rel(res.max_server_utilisation, ref.max_server_utilisation)]
    errs += [_rel(res.job_finish[j], ref.job_finish[j])
             for j in ref.job_finish]
    errs += [_rel(res.per_job_wait[j], ref.per_job_wait[j])
             for j in ref.per_job_wait]
    return max(errs)


def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


class PallasCalls:
    """Records every ``lindley_scan`` call the simulator makes — padded
    shape, dtype and interpret flag as passed — so the programs that
    actually ran can be lowered again and checked for a Mosaic custom
    call (an interpreted kernel lowers to none)."""

    def __init__(self):
        from repro.kernels import lindley_scan as mod
        self.mod, self.fn, self.calls = mod, mod.lindley_scan, set()

    def __enter__(self):
        def record(u, v, *, interpret):
            self.calls.add((u.shape, str(u.dtype), interpret))
            return self.fn(u, v, interpret=interpret)
        self.mod.lindley_scan = record
        return self

    def __exit__(self, *exc):
        self.mod.lindley_scan = self.fn

    def compiled(self) -> tuple[bool, str]:
        import jax
        ok, shapes = bool(self.calls), []
        for shape, dtype, interpret in sorted(self.calls):
            x = jax.ShapeDtypeStruct(shape, dtype)
            text = self.fn.lower(x, x, interpret=interpret).as_text()
            ok = ok and "tpu_custom_call" in text
            shapes.append("x".join(map(str, shape)))
        return ok, ",".join(shapes)


def static_phase(log: CompileLog, count_scale: float = 1.0) -> bool:
    """Device backends against the per-server ``loop`` reference.

    At full counts the snapshot saturates its busiest server, and numpy
    ``segmented`` itself strays from ``loop`` by up to ~4e-7 relative on
    a lightly loaded job's wait (its prefix sums run across all servers,
    so that job's small waits cancel against large sums); it is reported
    beside ``loop``, and the gate holds each device backend to ``loop``.
    """
    from repro.core.simulator import simulate, simulate_batch
    from sim_bench import live_workload, trial_placements
    spec, jobs, placement = live_workload(TRACE)
    cluster = spec.cluster
    trials = trial_placements(jobs, placement, K)
    kw = dict(count_scale=count_scale)
    ref, loop_s = _timed(lambda: simulate(jobs, placement, cluster,
                                          backend="loop", **kw))
    ref_b = [simulate(jobs, p, cluster, backend="loop", **kw)
             for p in trials]
    seg, seg_s = _timed(lambda: simulate(jobs, placement, cluster,
                                         backend="segmented", **kw))
    print(f"static: trace={TRACE} jobs={len(jobs)} "
          f"count_scale={count_scale} messages_per_call={ref.n_messages} "
          f"batch={K}x{ref.n_messages} loop_host_s={loop_s:.3f} "
          f"segmented_host_s={seg_s:.3f} "
          f"segmented_vs_loop max_rel_err={max_rel_err(seg, ref):.3e}")
    log.take()
    ok = True
    for be in ("jax", "pallas"):
        def one():
            return simulate(jobs, placement, cluster, backend=be, **kw)

        def batch():
            return simulate_batch(jobs, trials, cluster, backend=be, **kw)

        with PallasCalls() as pallas:
            _, setup_s = _timed(one)
            res, smoke_s = _timed(one)
            _, bsetup_s = _timed(batch)
            out, bsmoke_s = _timed(batch)
        err = max_rel_err(res, ref)
        berr = max(max_rel_err(r, f) for r, f in zip(out, ref_b))
        good = err <= TOL[be] and berr <= TOL[be]
        extra = ""
        if be == "pallas":
            compiled, shapes = pallas.compiled()
            good = good and compiled
            extra = f" pallas_compiled={compiled} pallas_shapes={shapes}"
        print(f"static[{be}]: vs_loop simulate max_rel_err={err:.3e} "
              f"batch max_rel_err={berr:.3e} tol={TOL[be]:.0e} "
              f"vs_segmented max_rel_err={max_rel_err(res, seg):.3e}{extra} "
              f"setup_first_call_s={setup_s:.3f} "
              f"smoke_warm_call_s={smoke_s:.3f} "
              f"setup_first_batch_s={bsetup_s:.3f} "
              f"smoke_warm_batch_s={bsmoke_s:.3f} {log.take()} "
              f"{'ok' if good else 'FAIL'}")
        ok = ok and good
    return ok


def run_scheduler(backend: str) -> dict:
    from repro import obs
    from repro.sched import (FleetScheduler, RemapConfig, SchedulerConfig,
                             get_trace)
    spec = get_trace(TRACE)
    sched = FleetScheduler(spec.cluster, "new", config=SchedulerConfig(
        remap=RemapConfig(interval=5.0), count_scale=spec.count_scale,
        sim_backend=backend))
    sched.submit_trace(spec.arrivals)
    with obs.recording(obs.Recorder(mode="ring", ring=1)) as rec:
        stats, wall_s = _timed(sched.run)
    sched.check_invariants()
    calls = rec.metrics.counter(f"sim.calls.{backend}").n
    msgs = rec.metrics.counter("sim.msgs").total
    placed = sorted((r["placed_at"], jid) for jid, r in stats.per_job.items()
                    if r["placed_at"] is not None)
    return {"stats": stats, "wall_s": wall_s, "calls": calls,
            "msgs": msgs, "admits": placed,
            "remaps": [(d.time, d.job_id, d.committed)
                       for d in sched.decisions]}


def scheduler_phase(log: CompileLog) -> bool:
    from repro.core.simulator import resolve_backend
    device = resolve_backend("auto")
    runs = {}
    for be in (device, "segmented"):
        r = run_scheduler(be)
        runs[be] = r
        per_call = r["msgs"] / r["calls"] if r["calls"] else 0.0
        print(f"scheduler[{be}]: trace={TRACE} jobs={r['stats'].n_jobs} "
              f"sim_calls={r['calls']} messages_per_call={per_call:.1f} "
              f"remap_commits={r['stats'].n_remap_commits} "
              f"makespan={r['stats'].makespan!r} "
              f"total_msg_wait={r['stats'].total_msg_wait!r} "
              f"smoke_wall_s={r['wall_s']:.3f} {log.take()}")
    d, s = runs[device], runs["segmented"]
    same_admits = [j for _, j in d["admits"]] == [j for _, j in s["admits"]]
    same_remaps = ([r[1:] for r in d["remaps"]]
                   == [r[1:] for r in s["remaps"]])
    times = [(a[0], b[0]) for a, b in zip(d["admits"], s["admits"])]
    times += [(a[0], b[0]) for a, b in zip(d["remaps"], s["remaps"])]
    time_err = max((_rel(a, b) for a, b in times), default=0.0)
    mk_err = _rel(d["stats"].makespan, s["stats"].makespan)
    mw_err = _rel(d["stats"].total_msg_wait, s["stats"].total_msg_wait)
    good = (device in ("jax", "pallas") and same_admits and same_remaps
            and max(time_err, mk_err, mw_err) <= SCHED_TOL)
    print(f"scheduler: auto={device} same_admits={same_admits} "
          f"same_remap_decisions={same_remaps} "
          f"decision_time_rel_err={time_err:.3e} "
          f"makespan_rel_err={mk_err:.3e} msg_wait_rel_err={mw_err:.3e} "
          f"tol={SCHED_TOL:.0e} {'ok' if good else 'FAIL'}")
    return good


def main() -> int:
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    from repro.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    log = CompileLog()
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(jax.devices())} jax={jax.__version__} "
          f"compile_cache={cache}")
    ok = static_phase(log)
    ok = scheduler_phase(log) and ok
    if not ok:
        print("chip_smoke: FAILED", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
