"""One run of one cell: set-up, the measured window, the check, the line.

Everything that belongs to one configuration, traffic mix or metric is
found by name from ``BENCHMARK.json``:

* ``bench/configs/<config>.json`` — the deployment (cluster and mix);
* ``bench/traffic/<traffic>.json`` — the traffic's parameters, which
  name its generator;
* ``bench/generators/<generator>.py`` — the generator that makes it
  (``harness/traffic.py``);
* ``bench/metrics/<metric>.py`` — a reader ``read(run)`` that returns the
  metric's value from the run's record, or ``None`` when the run has
  nothing for it to read.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


@dataclasses.dataclass
class Unit:
    kind: str
    t0: float
    t1: float
    sim_s: float
    calls: int

    @property
    def wall_s(self) -> float:
        return self.t1 - self.t0


@dataclasses.dataclass
class Run:
    """What a metric reader sees of one run."""

    cell: dict
    config: dict
    traffic: dict
    setup_s: float
    window_s: float
    units: list
    calls: list
    hops: int
    compiles: int
    trace: object = None
    peaks: dict | None = None


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_spec(workload: str) -> tuple[dict, dict, dict, dict]:
    spec = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {c["name"]: c for c in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    cfg = load_json(os.path.join(BENCH, "configs", cell["config"] + ".json"))
    traffic = load_json(os.path.join(BENCH, "traffic", cell["traffic"] + ".json"))
    return spec, cell, cfg, traffic


def cell_metrics(spec: dict, cell: dict) -> tuple[list, list]:
    """End-to-end and per-layer metric entries this cell reports."""
    name = cell["name"]
    e2e = [m for m in spec["end_to_end"] if name in m.get("workloads", [name])]
    reported = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"]
             if name in m.get("workloads", [name] if m["moves"] in reported else [])]
    return e2e, layer


def reader(metric: str):
    path = os.path.join(BENCH, "metrics", metric + ".py")
    mod_spec = importlib.util.spec_from_file_location(f"bench_metric_{metric}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def warm_device_shapes(rows: tuple, elements: int) -> int:
    """Compile (or load from the persistent cache) the simulator's device
    scan for every power-of-two row length from one block up to the
    cell's largest stage, at each batch height the cell's calls use.
    Returns how many shapes were warmed (0 on a host backend)."""
    from repro.core import sim_scan, simulator
    backend = simulator.resolve_backend("auto")
    device_waits = getattr(sim_scan, "_device_waits", None)
    if backend not in ("jax", "pallas") or device_waits is None:
        return 0
    top = max(1024, 1 << (max(elements, 1) - 1).bit_length())
    n, warmed = 1024, 0
    while n <= top:
        for b in sorted(set(rows)):
            device_waits(np.zeros((b, n)), np.full((b, n), -np.inf), backend)
            warmed += 1
        n *= 2
    return warmed


def log(msg: str) -> None:
    print(msg, flush=True)


def run_cell(args, t_start: float, fault=None, need_chip: bool = True,
             keep_trace: str | None = None) -> dict:
    """Set up, measure, check; returns the result line's object.

    ``args`` carries ``workload``, ``seed``, ``seconds``, ``trace``,
    ``rehearse`` and ``units`` (a fixed unit count in place of the timed
    window, for rehearsals and tests). ``fault`` breaks the simulator's
    answers underneath the timed path, and ``need_chip=False`` skips the
    look for a chip: both are for the benchmark's own tests, which run
    the rest of a run on the CPU. ``keep_trace`` copies the traced
    window's ``.xplane.pb`` there before it is deleted."""
    from . import check as checks
    from . import traffic as generators
    from .probe import CompileCounter, Probe

    spec, cell, cfg, traffic = load_spec(args.workload)
    e2e, layer = cell_metrics(spec, cell)

    t0 = time.perf_counter()
    import jax
    devices = jax.devices()
    dev = devices[0]
    if args.rehearse:
        if dev.platform != "cpu":
            raise SystemExit("rehearsal runs on the CPU only (JAX_PLATFORMS=cpu)")
    elif need_chip and (dev.platform != "tpu" or len(devices) < int(cell["chips"])):
        raise SystemExit(f"no accelerator for this cell: JAX platform {dev.platform!r} "
                         f"with {len(devices)} device(s), cell asks for {cell['chips']} TPU chip(s)")
    from repro.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    compiles = CompileCounter.get()
    t_jax = time.perf_counter() - t0
    log(f"device: platform={dev.platform} kind={dev.device_kind} count={len(devices)} "
        f"jax={jax.__version__} compile_cache={cache}")
    log(f"cell: {cell['name']} config={cell['config']} traffic={cell['traffic']} "
        f"generator={traffic['generator']} seed={args.seed} seconds={args.seconds} trace={args.trace}")

    t0 = time.perf_counter()
    probe = Probe()
    probe.fault = fault
    gen = generators.load(traffic["generator"])(cfg, traffic, args.seed, probe)
    built = gen.prepare()
    probe.install()
    t_build = time.perf_counter() - t0

    t0 = time.perf_counter()
    c0, h0, s0 = compiles.compiles, compiles.cache_hits, compiles.compile_s
    warmed = warm_device_shapes(gen.warm_rows, gen.warm_elements())
    for _ in range(int(traffic.get("warm_units", 0))):
        gen.unit()
    t_warm = time.perf_counter() - t0
    setup_s = time.perf_counter() - t_start
    log(f"setup: setup_s={setup_s!r} jax_init_s={t_jax:.3f} build_and_fill_s={t_build:.3f} "
        f"compile_or_load_s={t_warm:.3f} warm_shapes={warmed} programs={compiles.compiles - c0} "
        f"cache_hits={compiles.cache_hits - h0} backend_compile_s={compiles.compile_s - s0:.3f} "
        f"startup_s={setup_s - t_jax - t_build - t_warm:.3f} "
        + " ".join(f"{k}={v}" for k, v in built.items()))

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if args.trace else None
    probe.tracing = bool(args.trace)
    units: list[Unit] = []
    programs0 = compiles.programs
    gc.collect()
    if trace_dir:
        jax.profiler.start_trace(trace_dir)
    probe.recording = True
    limit = args.units or None
    with probe.span("bench.window"):
        w0 = time.perf_counter()
        while True:
            now = time.perf_counter()
            if limit is not None:
                if len(units) >= limit:
                    break
            elif now - w0 >= args.seconds:
                break
            n_calls = len(probe.calls)
            kind = gen.unit()
            t1 = time.perf_counter()
            sim_s = sum(c.wall_s for c in probe.calls[n_calls:])
            units.append(Unit(kind, now, t1, sim_s, len(probe.calls) - n_calls))
        w1 = time.perf_counter()
    probe.recording = False
    probe.tracing = False
    if trace_dir:
        jax.profiler.stop_trace()
    window_s = w1 - w0
    window_programs = compiles.programs - programs0
    thirds = [units[k * len(units) // 3:(k + 1) * len(units) // 3] for k in range(3)]
    log(f"window: window_s={window_s!r} over_seconds_s={window_s - args.seconds!r} "
        f"units={len(units)} sim_calls={len(probe.calls)} programs_built={window_programs} "
        "unit_ms_by_third=" + "/".join(
            f"{sum(u.wall_s for u in t) * 1e3 / max(len(t), 1):.2f}" for t in thirds))

    memory_peak = None
    if need_chip and not args.rehearse:
        memory_peak = max(int(d.memory_stats()["peak_bytes_in_use"])
                          for d in devices[:int(cell["chips"])])

    validity = gen.validity()
    row_of = gen.row_of
    gen.release()
    probe.uninstall()
    del gen
    gc.collect()

    from .reference import Topology
    topo = Topology(cfg["cluster"])
    hops = checks.count_hops(topo, probe.calls, row_of)
    work = checks.work_counts(units, probe.calls, hops)
    log("work: " + " ".join(f"{k}={v}" for k, v in work.items()))
    if args.rehearse:
        return {"work": work}

    reduction = None
    if trace_dir:
        from .trace import find_trace, reduce
        t0 = time.perf_counter()
        path = find_trace(trace_dir)
        reduction = reduce(path)
        if keep_trace:
            shutil.copyfile(path, keep_trace)
        shutil.rmtree(trace_dir, ignore_errors=True)
        log(f"trace: window_s={reduction.window_s!r} busy_s={reduction.busy_s!r} "
            f"scan_s={reduction.scan_s!r} device_ops={reduction.n_ops} chips={reduction.chips} "
            f"reduce_s={time.perf_counter() - t0:.3f}")

    verdict = checks.check(args.seed, traffic, topo, probe.calls, row_of, validity)

    from .peaks import peaks
    run = Run(cell=cell, config=cfg, traffic=traffic, setup_s=setup_s, window_s=window_s,
              units=units, calls=probe.calls, hops=sum(hops), compiles=window_programs,
              trace=reduction, peaks=peaks(dev.device_kind) if need_chip else None)
    metrics = {}
    for m in (layer if args.trace else e2e):
        value = reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": int(cell["chips"]), "memory_peak_bytes": memory_peak}
    out = {"correct": verdict["correct"], "attempted": len(units), "failed": 0,
           "metrics": metrics, "device": device}
    if reduction is not None:
        device["busy_s"] = reduction.busy_s
        device["window_s"] = reduction.window_s
        out["breakdown"] = {"device_ops": reduction.device_ops,
                            "idle_gaps": reduction.idle_gaps}
    out["check"] = verdict["numbers"]
    for name, num in verdict["numbers"].items():
        print(f"check: {name}={num['value']!r} limit={num['limit']!r}", file=sys.stderr)
    print(f"check: correct={verdict['correct']}", file=sys.stderr, flush=True)
    return out
