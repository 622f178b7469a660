"""Flight-recorder observability tests (DESIGN.md §11).

Covers the ``repro.obs`` layer itself (metrics instruments, recorder
modes, exporters/validators) and its integration contracts:

* per-mutation utilisation sampling — remap ticks on an unchanged fleet
  take no samples (the path-dependent-stats bugfix regression);
* ``FleetStats`` carries sample counts + the sampling policy label;
* two seeded identical runs dump **byte-identical** trace JSON, across
  simulator backends (the determinism acceptance);
* invariant failures carry the flight-recorder event tail.
"""
import importlib.util
import json
import sys

import numpy as np
import pytest

from repro import obs
from repro.core import AppGraph, ClusterTopology, simulate
from repro.core.graphs import FreeCoreTracker
from repro.core.simulator import SimHandle
from repro.obs.export import (to_chrome, to_csv, validate_chrome,
                              validate_native)
from repro.search import search_placement
from repro.sched import (FleetScheduler, SchedulerConfig,
                         SchedulerInvariantError, get_trace)

KB = 1 << 10


def _job(job_id, procs=8, pattern="all_to_all"):
    return AppGraph.from_pattern(f"j{job_id}", pattern, procs, 64 * KB,
                                 10.0, 20, job_id=job_id)


def _run_fleet(remap_interval=None, strategy="blocked", sim_backend="auto",
               n_arrivals=6, recorder=None, seed=3, rate=0.3, **sched_kw):
    spec = get_trace("rack_oversub", seed=seed, rate=rate,
                     n_arrivals=n_arrivals)
    sched = FleetScheduler(spec.cluster, strategy,
                           config=SchedulerConfig.from_legacy(
                               remap_interval=remap_interval,
                               state_bytes_per_proc=spec.state_bytes_per_proc,
                               count_scale=spec.count_scale,
                               sim_backend=sim_backend, **sched_kw),
                           recorder=recorder)
    sched.submit_trace(spec.arrivals)
    stats = sched.run()
    sched.check_invariants()
    return sched, stats


# ---------------------------------------------------------------------------
# Metrics instruments
# ---------------------------------------------------------------------------
def test_metrics_instrument_basics():
    m = obs.Metrics()
    m.counter("calls").inc()
    m.counter("calls").inc(3)
    m.gauge("depth").set(2, t=1.0)
    m.gauge("depth").set(5, t=2.0)
    m.histogram("util").observe(0.5)
    m.histogram("util").observe(1.5)
    m.series("links").append(0.0, np.array([0.1, 0.9]))
    m.series("links").append(1.0, np.array([0.2, 0.4]))

    assert m.counter("calls").total == 4 and m.counter("calls").n == 2
    assert m.gauge("depth").value == 5 and m.gauge("depth").summary()["max"] == 5
    assert m.histogram("util").n == 2
    assert m.histogram("util").percentile(50) == 1.0
    # series percentile pools every link at every tick uniformly
    assert m.series("links").n == 2
    assert m.series("links").concat().size == 4
    assert m.series("links").percentile(100) == 0.9
    assert m.sample_counts() == {"calls": 2, "depth": 2, "links": 2, "util": 2}
    assert m.names() == ["calls", "depth", "links", "util"]


def test_metrics_kind_mismatch_raises():
    m = obs.Metrics()
    m.counter("x")
    with pytest.raises(TypeError, match="already registered"):
        m.gauge("x")


# ---------------------------------------------------------------------------
# Recorder
# ---------------------------------------------------------------------------
def test_ring_mode_keeps_only_the_tail():
    rec = obs.Recorder(mode="ring", ring=4)
    for i in range(10):
        rec.instant(f"e{i}", ts=float(i))
    assert rec.n_events() == 4
    lines = rec.flight_lines()
    assert len(lines) == 4 and "e9" in lines[-1] and "e6" in lines[0]
    dump = rec.flight_dump()
    assert dump.startswith("-- flight recorder: last 4 events --")


def test_disabled_recorder_records_nothing():
    rec = obs.Recorder(enabled=False)
    rec.instant("a")
    rec.span("b", ts=0.0, dur=1.0)
    rec.counter("c", 1.0)
    assert rec.n_events() == 0 and rec.flight_dump() == ""


def test_install_recording_and_from_env():
    assert obs.current() is obs.NULL and not obs.current().enabled
    with obs.recording() as rec:
        assert obs.current() is rec and rec.enabled
        with obs.recording(obs.Recorder(mode="ring")) as inner:
            assert obs.current() is inner
        assert obs.current() is rec
    assert obs.current() is obs.NULL

    assert obs.from_env({}) is None
    assert obs.from_env({"REPRO_TRACE": "0"}) is None
    assert obs.from_env({"REPRO_TRACE": "1"}).mode == "full"
    ring = obs.from_env({"REPRO_TRACE": "ring", "REPRO_TRACE_RING": "7"})
    assert ring.mode == "ring" and ring.ring == 7


# ---------------------------------------------------------------------------
# Exporters + validators (the CI trace-schema gate)
# ---------------------------------------------------------------------------
def _sample_doc():
    rec = obs.Recorder()
    rec.set_process("sched:new")
    rec.instant("admit", ts=0.0, job=1)
    rec.span("job:1", ts=0.0, dur=2.5, track="job:001")
    rec.counter("util.level.rack", {"max": 0.5, "mean": 0.25}, ts=1.0)
    rec.set_process("sim")
    rec.instant("simulate", cat=obs.CAT_SIM, ts=1.0, backend="loop")
    return rec.dump()


def test_chrome_export_structure_and_determinism():
    doc = _sample_doc()
    chrome = to_chrome(doc)
    assert chrome == to_chrome(json.loads(json.dumps(doc)))
    evs = chrome["traceEvents"]
    meta = [e for e in evs if e["ph"] == "M"]
    procs = {e["args"]["name"]: e["pid"] for e in meta
             if e["name"] == "process_name"}
    # pids assigned in sorted proc-label order, 1-based
    assert procs == {"sched:new": 1, "sim": 2}
    spans = [e for e in evs if e["ph"] == "X"]
    assert spans[0]["dur"] == 2.5e6 and spans[0]["ts"] == 0.0
    instants = [e for e in evs if e["ph"] == "i"]
    assert all(e["s"] == "t" for e in instants)
    assert validate_chrome(chrome) == []


def test_csv_export_long_format():
    csv = to_csv(_sample_doc())
    lines = csv.strip().split("\n")
    assert lines[0] == "proc,series,time_s,key,value"
    assert "sched:new,util.level.rack,1.0,max,0.5" in lines
    assert "sched:new,util.level.rack,1.0,mean,0.25" in lines
    assert len(lines) == 3  # only the util.* counter rows


def test_validators_catch_corruption():
    doc = _sample_doc()
    assert validate_native(doc) == []
    bad = json.loads(json.dumps(doc))
    bad["events"][0]["ts"] = -1.0
    bad["events"][1]["ph"] = "Z"
    del bad["format"]
    probs = validate_native(bad)
    assert len(probs) >= 3
    assert any("ts" in p for p in probs)
    assert any("phase" in p for p in probs)

    chrome = to_chrome(doc)
    del chrome["traceEvents"][-1]["pid"]
    assert any("pid" in p for p in validate_chrome(chrome))
    assert validate_chrome({"events": []}) == ["missing traceEvents list"]


def test_export_cli_roundtrip(tmp_path, capsys):
    from repro.obs import export
    src = tmp_path / "trace.json"
    src.write_text(json.dumps(_sample_doc()))
    out = tmp_path / "trace.perfetto.json"
    export.main([str(src), "--format", "perfetto", "--out", str(out)])
    chrome = json.loads(out.read_text())
    assert validate_chrome(chrome) == []
    export.main([str(src), "--format", "validate"])
    assert "valid repro-trace-v1" in capsys.readouterr().err
    with pytest.raises(SystemExit):
        export.main([str(src.with_suffix(".missing"))])


# ---------------------------------------------------------------------------
# Scheduler integration: per-mutation sampling (the bugfix regression)
# ---------------------------------------------------------------------------
def test_remap_ticks_without_commits_take_no_samples():
    """The path-dependency bugfix: utilisation stats must be a function
    of the fleet mutation sequence, not of how often the remap timer
    fired. ``blocked`` on this trace evaluates remaps but commits none,
    so the remap-tick run must sample exactly like the no-remap run."""
    _, base = _run_fleet(remap_interval=None)
    _, ticked = _run_fleet(remap_interval=2.0)
    assert ticked.n_remap_commits == 0 and ticked.n_remap_rejects > 0
    assert ticked.sample_counts == base.sample_counts
    assert ticked.level_p99_util == base.level_p99_util
    assert ticked.nic_p99_util == base.nic_p99_util
    assert ticked.peak_sim_util == base.peak_sim_util


def test_committed_remap_is_a_sampled_mutation():
    """A remap that actually moves jobs IS a fleet mutation and adds at
    least one sample per commit (commits also shift later departures, so
    the downstream mutation sequence may add more). The budgeted-search
    remap on the denser seed-0 trace is the committed-remap scenario the
    goldens pin: under the wait-rate migration pricing (DESIGN.md §13)
    the lighter seed-3 trace's marginal moves are rejected — correctly."""
    kw = dict(strategy="new", n_arrivals=10, seed=0, rate=0.5,
              remap_budget=64)
    _, base = _run_fleet(remap_interval=None, **kw)
    _, remapped = _run_fleet(remap_interval=5.0, **kw)
    assert remapped.n_remap_commits > 0
    extra = (remapped.sample_counts["peak_sim_util"]
             - base.sample_counts["peak_sim_util"])
    assert extra >= remapped.n_remap_commits


def test_fleet_stats_sampling_metadata():
    _, stats = _run_fleet()
    assert stats.sampling_policy == "per-mutation"
    counts = stats.sample_counts
    assert counts["peak_sim_util"] > 0
    assert counts["nic_util"] == counts["peak_sim_util"]
    for level in stats.level_p99_util:
        assert counts[f"level.{level}"] == counts["nic_util"]
    assert stats.to_dict()["sample_counts"] == counts


# ---------------------------------------------------------------------------
# Determinism acceptance: byte-identical dumps across seeded runs
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("backend", [
    "loop", "segmented",
    pytest.param("jax", marks=pytest.mark.skipif(
        importlib.util.find_spec("jax") is None,
        reason="jax not installed")),
])
def test_sched_trace_dumps_are_byte_identical(backend):
    dumps = []
    for _ in range(2):
        with obs.recording() as rec:
            sched, _ = _run_fleet(remap_interval=2.0, strategy="new",
                                  sim_backend=backend, n_arrivals=5)
            dumps.append(rec.dump_json(
                extra_metrics={"sched": sched.metrics}))
    assert dumps[0] == dumps[1]
    assert json.loads(dumps[0])["format"] == "repro-trace-v1"
    # and the exported Perfetto doc is equally deterministic
    chromes = [json.dumps(to_chrome(json.loads(d)), sort_keys=True)
               for d in dumps]
    assert chromes[0] == chromes[1]


def test_sched_trace_backends_agree_on_event_stream():
    """Backends may differ in float dust inside payloads, but the event
    *sequence* (names, categories, sim timestamps) must match."""
    streams = []
    for backend in ("loop", "segmented"):
        with obs.recording() as rec:
            _run_fleet(remap_interval=2.0, strategy="new",
                       sim_backend=backend, n_arrivals=5)
            streams.append([(e.name, e.cat, e.ph, round(e.ts, 9))
                            for e in rec.events
                            if e.cat != obs.CAT_SIM])
    assert streams[0] == streams[1]


def test_search_trace_dumps_are_byte_identical():
    cluster = ClusterTopology(n_nodes=4)
    jobs = [_job(0, 8), _job(1, 8, "linear")]
    # warm the process-level flat-message cache so both traced runs see
    # identical cache state (hit counts are part of the dump)
    search_placement(jobs, cluster, FreeCoreTracker(cluster),
                     seed="blocked", budget=24, rng_seed=5)
    dumps = []
    for _ in range(2):
        with obs.recording() as rec:
            search_placement(jobs, cluster, FreeCoreTracker(cluster),
                             seed="blocked", budget=24, rng_seed=5)
            dumps.append(rec.dump_json())
    assert dumps[0] == dumps[1]
    names = {e["name"] for e in json.loads(dumps[0])["events"]}
    assert {"search_begin", "search_seeds", "search_end"} <= names


# ---------------------------------------------------------------------------
# Simulator provenance + flight recorder on invariant failure
# ---------------------------------------------------------------------------
def test_simulator_records_call_provenance():
    job = _job(0, 4)
    cluster = ClusterTopology(n_nodes=2)
    tracker = FreeCoreTracker(cluster)
    from repro.core import STRATEGIES
    placement = STRATEGIES["blocked"]([job], cluster, tracker=tracker)
    with obs.recording() as rec:
        simulate([job], placement, cluster, backend="loop")
        handle = SimHandle(cluster, backend="segmented")
        handle.simulate([job], placement)   # cold: builds the flat cache
        handle.simulate([job], placement)   # warm: reuses it
    m = rec.metrics
    assert m.counter("sim.calls.loop").n == 1
    assert m.counter("sim.calls.segmented").n == 2
    assert m.counter("sim.msgs").total > 0
    sims = [e for e in rec.events if e.cat == obs.CAT_SIM]
    assert [e.args.get("warm") for e in sims] == [False, False, True]


def test_invariant_failure_carries_flight_tail():
    with obs.recording() as rec:
        sched, _ = _run_fleet(n_arrivals=4)
        # the trace drained; admit a fresh job, then corrupt the
        # accounting by stealing its placement entry
        job = sched.admit(_job(99, 4))
        del sched.placement.assignments[job.job_id]
        with pytest.raises(SchedulerInvariantError) as ei:
            sched.check_invariants()
    assert rec.n_events() > 0
    tail = rec.flight_dump()
    assert "admit" in tail and "depart" in tail
    if sys.version_info >= (3, 11):
        notes = getattr(ei.value, "__notes__", [])
        assert any("flight recorder" in n for n in notes)
