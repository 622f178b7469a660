"""Host-clock spans (``repro.obs.host``): the ring, its summaries, and
the spans the simulator, scheduler and search open (DESIGN.md §11)."""
import glob
import os
import time

import numpy as np
import pytest

from repro import obs
from repro.core import ClusterTopology, NetLevel, NetworkHierarchy, Placement
from repro.core import sim_scan
from repro.core.graphs import AppGraph, PATTERNS
from repro.obs.host import HostSpans

SIM_PHASES = ("sim.flatten", "sim.route", "sim.order", "sim.pack",
              "sim.device", "sim.reduce")


def _records(ring, t0=-np.inf):
    r = ring.records(t0)
    return {int(s): (str(n), int(p), int(root), float(a), float(b), float(x))
            for s, n, p, root, a, b, x in zip(r["seq"], r["name"], r["parent"],
                                              r["root"], r["start"], r["end"],
                                              r["self_s"])}


def _workload(seed: int, cluster: ClusterTopology, n_jobs: int = 8):
    rng = np.random.default_rng(seed)
    free = list(rng.permutation(cluster.n_cores))
    jobs, placement = [], Placement(cluster)
    for jid in range(n_jobs):
        procs = int(rng.integers(2, 9))
        if procs > len(free):
            break
        jobs.append(AppGraph.from_pattern(
            f"j{jid}", PATTERNS[jid % len(PATTERNS)], procs,
            float(rng.choice([256.0, 64 * 1024.0, 2 * 1024 * 1024.0])),
            float(rng.uniform(5.0, 200.0)), int(rng.integers(2, 30)), job_id=jid))
        placement.assign(jid, np.array([free.pop() for _ in range(procs)], dtype=np.int64))
    return jobs, placement


def _shuffled(seed: int, cluster: ClusterTopology, jobs, k: int):
    """``k`` placements of ``jobs``, each a fresh random core draw."""
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(k):
        cores = rng.permutation(cluster.n_cores)
        p, off = Placement(cluster), 0
        for job in jobs:
            p.assign(job.job_id, cores[off:off + job.n_procs].astype(np.int64))
            off += job.n_procs
        out.append(p)
    return out


def _two_level():
    return ClusterTopology(n_nodes=16, hierarchy=NetworkHierarchy(
        [NetLevel("nic", fan_in=1, bw=1e9, latency=1e-7),
         NetLevel("rack", fan_in=4, bw=4e9, latency=5e-7)]))


# ---------------------------------------------------------------------------
# The ring
# ---------------------------------------------------------------------------
def test_nested_spans_self_time_parent_and_root():
    ring = HostSpans(capacity=64)
    with ring.span("a"):
        with ring.span("b"):
            with ring.span("c"):
                time.sleep(0.002)
        with ring.span("b"):
            time.sleep(0.001)
    with ring.span("d"):
        pass
    recs = _records(ring)
    assert [recs[s][0] for s in sorted(recs)] == ["a", "b", "c", "b", "d"]
    (a, b1, c, b2, d) = (recs[s] for s in sorted(recs))
    # parent and root are the records' numbers, in opening order
    assert [r[1] for r in (a, b1, c, b2, d)] == [-1, 0, 1, 0, -1]
    assert [r[2] for r in (a, b1, c, b2, d)] == [0, 0, 0, 0, 4]
    dur = {s: r[4] - r[3] for s, r in recs.items()}
    assert recs[2][5] == pytest.approx(dur[2], abs=1e-12)          # leaf
    assert recs[1][5] == pytest.approx(dur[1] - dur[2], abs=1e-12)
    assert recs[0][5] == pytest.approx(dur[0] - dur[1] - dur[3], abs=1e-12)
    assert all(r[5] >= 0 for r in recs.values())
    assert dur[2] >= 0.002 and recs[0][5] < dur[2]


def test_count_and_summary_totals():
    ring = HostSpans(capacity=64)
    t0 = time.perf_counter()
    for nbytes in (100, 250):
        with ring.span("sim.device") as s:
            s.count(nbytes)
            with ring.span("inner") as s2:
                s2.count(7)
            s.count(1)          # the innermost open span again
    got = ring.summary(t0, time.perf_counter())
    assert got["sim.device"].n == 2 and got["sim.device"].count == 352
    assert got["inner"] == obs.SpanTotals(2, got["inner"].self_s, 14)
    assert got["sim.device"].self_s >= 0


def test_summary_filters_by_start_in_window():
    ring = HostSpans(capacity=64)
    with ring.span("before"):
        pass
    t0 = time.perf_counter()
    with ring.span("inside"):
        with ring.span("child"):
            pass
    t1 = time.perf_counter()
    with ring.span("after"):
        pass
    assert set(ring.summary(t0, t1)) == {"inside", "child"}
    assert set(ring.summary(-np.inf, np.inf)) == {"before", "inside", "child", "after"}
    assert ring.summary(t1, t1) == {}


def test_overflow_gives_none_and_later_windows_stay_readable():
    ring = HostSpans(capacity=8)
    t0 = time.perf_counter()
    for _ in range(8):
        with ring.span("x"):
            pass
    assert ring.summary(t0, time.perf_counter())["x"].n == 8   # just fits
    with ring.span("x"):                                       # drops the first
        pass
    assert ring.summary(t0, time.perf_counter()) is None
    t_late = time.perf_counter()
    for _ in range(3):
        with ring.span("y"):
            pass
    assert ring.summary(t_late, time.perf_counter()) == {
        "y": obs.SpanTotals(3, ring.summary(t_late, np.inf)["y"].self_s, 0)}
    assert len(ring.records()["seq"]) == 8
    with pytest.raises(ValueError):
        HostSpans(capacity=12)


def test_span_records_and_unwinds_on_exception():
    ring = HostSpans(capacity=16)
    with pytest.raises(KeyError):
        with ring.span("outer"):
            with ring.span("boom"):
                raise KeyError("x")
    with ring.span("next"):
        pass
    recs = _records(ring)
    assert [recs[s][0] for s in sorted(recs)] == ["outer", "boom", "next"]
    assert recs[2][1] == -1 and recs[2][2] == 2      # the stack unwound


# ---------------------------------------------------------------------------
# The simulator's spans
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("k", [1, 16])
@pytest.mark.parametrize("topology", ["flat", "two_level"])
def test_records_per_simulator_call_bounded(monkeypatch, k, topology):
    """At most 24 records per simulator call on the device path, whatever
    the batch: the phases loop over the K placements inside one span."""
    monkeypatch.setenv("REPRO_SIM_BACKEND", "jax")
    from repro.core.simulator import SimHandle, simulate, simulate_batch
    cluster = ClusterTopology(n_nodes=16) if topology == "flat" else _two_level()
    jobs, base = _workload(3, cluster)
    pls = [base] + _shuffled(4, cluster, jobs, k - 1)
    handle = SimHandle(cluster)
    calls = (lambda: simulate(jobs, base, cluster),
             lambda: simulate_batch(jobs, pls, cluster),
             lambda: handle.simulate(jobs, base),
             lambda: handle.simulate_batch(jobs, pls))
    for call in calls:
        t0 = time.perf_counter()
        call()
        got = obs.host_summary(t0, time.perf_counter())
        assert got["sim.call"].n == 1
        assert sum(t.n for t in got.values()) <= 24, got
        assert set(SIM_PHASES[1:]) <= set(got), got
        assert got["sim.device"].count > 0


def test_batch_loop_bit_identical_to_single_calls():
    """Each phase loops over the K placements: every answer of one
    batched call is bit-identical to its placement's own call."""
    for cluster in (ClusterTopology(n_nodes=8), _two_level()):
        jobs, base = _workload(11, cluster)
        pls = [base] + _shuffled(12, cluster, jobs, 6)
        batch = sim_scan.simulate_scan_batch(jobs, pls, cluster, backend="jax")
        for p, got in zip(pls, batch):
            one = sim_scan.simulate_scan_batch(jobs, [p], cluster, backend="jax")[0]
            assert got == one
            assert got == sim_scan.simulate_scan(jobs, p, cluster, backend="jax")


def test_span_names_on_profiler_host_plane(tmp_path, monkeypatch):
    """Under a profiler session every span is also a TraceAnnotation on
    the host plane of the same ``.xplane.pb`` as the device ops."""
    import jax
    from jax.profiler import ProfileData
    monkeypatch.setenv("REPRO_SIM_BACKEND", "jax")
    from repro.core.simulator import simulate
    cluster = ClusterTopology(n_nodes=8)
    jobs, base = _workload(5, cluster)
    simulate(jobs, base, cluster)                  # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        simulate(jobs, base, cluster)
    path, = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"), recursive=True)
    prof = ProfileData.from_file(path)
    names = {ev.name for plane in prof.planes if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events}
    assert {"sim.call", *SIM_PHASES} <= names
    assert not jax.profiler.TraceAnnotation.is_enabled()   # inert again


def test_lowered_scan_module_is_named_scan():
    """The trace reduction finds the device scan by ``scan`` in its
    module name; the jitted function keeps that name."""
    import jax
    import jax.numpy as jnp
    with jax.enable_x64(True):
        chunk = jnp.zeros((1, 1024))              # one chunk: npad 1024
        lowered = sim_scan._jax_scan_fn().lower(chunk)
    assert "jit_scan" in lowered.as_text().split("\n", 1)[0]


# ---------------------------------------------------------------------------
# Scheduler and search spans
# ---------------------------------------------------------------------------
def test_scheduler_events_are_span_roots_and_stay_out_of_the_recorder():
    from repro.sched import FleetScheduler, SchedulerConfig
    from repro.sched.traces import get_trace
    spec = get_trace("rack_oversub", seed=3, rate=0.3, n_arrivals=6)
    sched = FleetScheduler(spec.cluster, "blocked", config=SchedulerConfig.from_legacy(
        remap_interval=2.0, state_bytes_per_proc=spec.state_bytes_per_proc,
        count_scale=spec.count_scale))
    sched.submit_trace(spec.arrivals)
    t0 = time.perf_counter()
    with obs.recording() as rec:
        sched.run()
    r = obs.host_records(t0)
    names = list(r["name"])
    by_seq = dict(zip(r["seq"].tolist(), names))
    roots = {n for n, p in zip(names, r["parent"]) if p == -1}
    assert roots <= {"sched.arrival", "sched.departure", "sched.remap"}
    assert {"sched.arrival", "sched.departure", "sched.remap", "sched.admission",
            "sched.reclock", "sched.remap_pass", "sim.call"} <= set(names)
    # every simulator call of the run hangs under one scheduler event
    for n, root in zip(names, r["root"]):
        if n.startswith("sim."):
            assert by_seq[int(root)].startswith("sched.")
    assert not {e.name for e in rec.events} & set(names)
    assert all("wall" not in d for d in rec.dump()["events"])



def test_each_remap_pass_is_one_pass_span_under_one_event_root():
    from repro.sched import FleetScheduler, SchedulerConfig
    from repro.sched.events import REMAP
    from repro.sched.traces import get_trace
    spec = get_trace("rack_oversub", seed=3, rate=0.3, n_arrivals=6)
    sched = FleetScheduler(spec.cluster, "blocked", config=SchedulerConfig.from_legacy(
        remap_interval=2.0, state_bytes_per_proc=spec.state_bytes_per_proc,
        count_scale=spec.count_scale))
    sched.submit_trace(spec.arrivals)
    t0 = time.perf_counter()
    n_passes = 0
    while (ev := sched.step()) is not None:
        n_passes += ev.kind == REMAP
    got = obs.host_summary(t0, time.perf_counter())
    assert n_passes > 0
    assert got["sched.remap"].n == n_passes
    assert got["sched.remap_pass"].n == n_passes

def test_search_run_is_the_root_of_its_scoring():
    from repro.search.optimizer import search_placement
    cluster = ClusterTopology(n_nodes=16)
    jobs, _ = _workload(7, cluster, n_jobs=4)
    t0 = time.perf_counter()
    search_placement(jobs, cluster, None, budget=12, population=4)
    r = obs.host_records(t0)
    assert r["name"][0] == "search.run" and r["parent"][0] == -1
    assert (r["root"] == r["seq"][0]).all()
    got = obs.host_summary(t0, time.perf_counter())
    assert got["search.run"].n == 1 and got["sim.call"].n >= 2
