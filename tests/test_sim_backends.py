"""Differential fuzz layer: loop vs segmented vs jax (vs pallas).

Randomized workloads — sizes, rates, message counts, live-sets, and
(multi-level) network hierarchies — drive every backend and require the
f64 backends (``loop``/``segmented``/``jax``) to agree to 1e-9 on every
metric; the float32 Pallas kernel is held to a looser tolerance. The
deliberately-tied workloads at the bottom pin the tie-repair semantics
that random fuzzing would only hit by accident.
"""
import time

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # pinned image lacks hypothesis — deterministic fallback
    from repro.testing import given, settings, strategies as st

from repro.core import (ClusterTopology, NetLevel, NetworkHierarchy,
                        Placement, default_hierarchy, simulate,
                        simulate_batch)
from repro.core.graphs import AppGraph, PATTERNS, tie_phase
from repro.core.simulator import BACKENDS, resolve_backend

KB = 1 << 10
MB = 1 << 20

# f64 backends re-associate the same sums (1e-9 required by the
# differential-fuzz contract); pallas is f32
TOL = {"segmented": 1e-9, "jax": 1e-9, "pallas": 2e-3}


def _random_workload(rng: np.random.Generator, cluster: ClusterTopology,
                     n_jobs: int, lengths=(256.0, 64 * KB, 2 * MB)):
    """Random jobs + a random valid placement on the cluster."""
    jobs, used = [], []
    free = list(range(cluster.n_cores))
    rng.shuffle(free)
    placement = Placement(cluster)
    for jid in range(n_jobs):
        procs = int(rng.integers(2, 9))
        if procs > len(free):
            break
        pattern = PATTERNS[int(rng.integers(0, len(PATTERNS)))]
        length = float(rng.choice(lengths))
        rate = float(rng.uniform(5.0, 200.0))
        count = int(rng.integers(1, 30))
        job = AppGraph.from_pattern(f"j{jid}", pattern, procs, length, rate,
                                    count, job_id=jid)
        cores = np.array([free.pop() for _ in range(procs)], dtype=np.int64)
        placement.assign(jid, cores)
        jobs.append(job)
        used.append(cores)
    return jobs, placement


def _assert_close(a, b, rtol, what):
    assert a == pytest.approx(b, rel=rtol, abs=rtol), \
        f"{what}: {a} vs {b}"


def _check_all_backends(jobs, placement, cluster, count_scale=1.0,
                        backends=("segmented", "jax")):
    base = simulate(jobs, placement, cluster, count_scale, backend="loop")
    for be in backends:
        res = simulate(jobs, placement, cluster, count_scale, backend=be)
        rtol = TOL[be]
        _assert_close(res.total_wait, base.total_wait, rtol,
                      f"{be} total_wait")
        _assert_close(res.workload_finish, base.workload_finish, rtol,
                      f"{be} workload_finish")
        # utilisation is busy/span — ill-conditioned exactly at
        # saturation (span -> busy), where last-bit wait differences
        # amplify by 1/idle-fraction; dimensionless, so a small ABSOLUTE
        # tolerance is the honest comparison there
        assert res.max_server_utilisation == pytest.approx(
            base.max_server_utilisation, rel=rtol, abs=max(rtol, 1e-6)), \
            f"{be} util: {res.max_server_utilisation} vs " \
            f"{base.max_server_utilisation}"
        assert res.n_messages == base.n_messages
        for jid in base.job_finish:
            _assert_close(res.job_finish[jid], base.job_finish[jid], rtol,
                          f"{be} job_finish[{jid}]")
            _assert_close(res.per_job_wait[jid], base.per_job_wait[jid],
                          max(rtol, rtol * base.per_job_wait[jid]),
                          f"{be} per_job_wait[{jid}]")
    return base


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 10_000), st.integers(1, 6))
def test_backends_agree_random_workloads(seed, n_jobs):
    rng = np.random.default_rng(seed)
    cluster = ClusterTopology(n_nodes=4)
    jobs, placement = _random_workload(rng, cluster, n_jobs)
    if not jobs:
        return
    _check_all_backends(jobs, placement, cluster)


@settings(max_examples=8, deadline=None)
@given(st.integers(0, 10_000))
def test_backends_agree_ici_pod_path(seed):
    """TPU-fleet routing: same-pod ICI + pod-crossing NIC, both rounds."""
    rng = np.random.default_rng(seed)
    cluster = ClusterTopology(n_nodes=8, pods=2, ici_bw=50e9,
                              cache_msg_cap=float(1 << 19))
    jobs, placement = _random_workload(rng, cluster, 4)
    if not jobs:
        return
    base = _check_all_backends(jobs, placement, cluster)
    assert base.n_messages > 0


def _random_hierarchy(rng: np.random.Generator,
                      cores_per_node: int, n_nodes: int) -> NetworkHierarchy:
    """Random multi-level tree over the cluster: node level plus 1–3
    outer levels with random fan-in, bandwidth, latency, express flags
    and attach granularity. Bandwidths stay >= 4 GB/s so random
    workloads cannot drive a server into sustained overload, where queue
    dynamics amplify the backends' benign last-bit rounding differences
    past any fixed tolerance (see the saturation stress test below)."""
    levels = [NetLevel("node", fan_in=cores_per_node,
                       bw=float(rng.uniform(4e9, 50e9)),
                       latency=float(rng.choice([0.0, 1e-7, 1e-6])))]
    group_nodes = 1          # nodes per group at the innermost level
    for k in range(int(rng.integers(1, 4))):
        fan = int(rng.integers(2, 4))
        if group_nodes * fan > n_nodes:
            break
        group_nodes *= fan
        express = bool(rng.random() < 0.4)
        attach = None
        if express and rng.random() < 0.5:
            attach = cores_per_node       # per-node direct links
        levels.append(NetLevel(
            f"l{k}", fan_in=fan, bw=float(rng.uniform(4e9, 20e9)),
            latency=float(rng.choice([0.0, 1e-7, 5e-7])),
            express=express, attach_cores=attach))
    return NetworkHierarchy(levels)


@settings(max_examples=12, deadline=None)
@given(st.integers(0, 10_000), st.integers(1, 6))
def test_backends_agree_random_hierarchy(seed, n_jobs):
    """The multi-level LCA path: random trees (depth 2–4, random express
    levels / attach granularity) must agree across f64 backends to 1e-9."""
    rng = np.random.default_rng(seed)
    n_nodes = int(rng.choice([8, 12, 16]))
    cluster = ClusterTopology(n_nodes=n_nodes, sockets_per_node=2,
                              cores_per_socket=2,
                              cache_msg_cap=float(rng.choice([1 << 19,
                                                              1 << 62])))
    cluster.hierarchy = _random_hierarchy(rng, cluster.cores_per_node,
                                          n_nodes)
    jobs, placement = _random_workload(rng, cluster, n_jobs,
                                       lengths=(256.0, 64 * KB, 512 * KB))
    if not jobs:
        return
    _check_all_backends(jobs, placement, cluster)


@settings(max_examples=6, deadline=None)
@given(st.integers(0, 10_000))
def test_backends_agree_saturated_hierarchy_loose(seed):
    """Sustained-overload stress: 2 MB messages through sub-GB/s uplinks.
    Queue dynamics amplify last-bit rounding between the backends'
    (mathematically identical) scan formulations, so agreement is only
    asserted to 1e-6 here — the 1e-9 contract applies to stable loads."""
    rng = np.random.default_rng(seed)
    cluster = ClusterTopology(n_nodes=8, sockets_per_node=2,
                              cores_per_socket=2)
    cluster.hierarchy = NetworkHierarchy([
        NetLevel("node", fan_in=4, bw=float(rng.uniform(5e8, 2e9)),
                 latency=1e-7),
        NetLevel("rack", fan_in=2, bw=float(rng.uniform(5e8, 2e9)),
                 latency=3e-7),
        NetLevel("pod", fan_in=4, bw=float(rng.uniform(5e8, 2e9)),
                 latency=1e-6),
    ])
    jobs, placement = _random_workload(rng, cluster, 4)
    if not jobs:
        return
    base = simulate(jobs, placement, cluster, backend="loop")
    for be in ("segmented", "jax"):
        res = simulate(jobs, placement, cluster, backend=be)
        _assert_close(res.total_wait, base.total_wait, 1e-6,
                      f"{be} total_wait")
        _assert_close(res.workload_finish, base.workload_finish, 1e-6,
                      f"{be} workload_finish")


@settings(max_examples=8, deadline=None)
@given(st.integers(0, 10_000))
def test_backends_agree_live_set_churn(seed):
    """Random live-sets: start from a full random workload, then remove a
    random subset of jobs (simulating departures) and re-check agreement
    on the fragmented remainder — the scheduler's steady-state shape."""
    rng = np.random.default_rng(seed)
    cluster = ClusterTopology(n_nodes=6)
    jobs, placement = _random_workload(rng, cluster, 6)
    if len(jobs) < 2:
        return
    keep = sorted(rng.choice(len(jobs), size=int(rng.integers(1, len(jobs))),
                             replace=False).tolist())
    live = [jobs[i] for i in keep]
    p = Placement(cluster)
    for job in live:
        p.assign(job.job_id, placement.assignments[job.job_id])
    _check_all_backends(live, p, cluster)


@settings(max_examples=8, deadline=None)
@given(st.integers(0, 10_000))
def test_two_level_hierarchy_reproduces_flat_tpu_model(seed):
    """Acceptance pin: an explicit 2-level NetworkHierarchy configured as
    node-NIC + pod-DCN reproduces the pre-hierarchy (PR 2) simulator
    outputs to 1e-9 across all f64 backends."""
    rng = np.random.default_rng(seed)
    flat = ClusterTopology(n_nodes=8, pods=2, ici_bw=50e9,
                           cache_msg_cap=float(1 << 19))
    explicit = ClusterTopology(n_nodes=8, pods=2, ici_bw=50e9,
                               cache_msg_cap=float(1 << 19))
    explicit.hierarchy = NetworkHierarchy([
        NetLevel("node", fan_in=flat.cores_per_node, bw=flat.ici_bw,
                 latency=flat.switch_latency),
        NetLevel("pod", fan_in=flat.nodes_per_pod, bw=flat.nic_bw,
                 latency=flat.switch_latency, express=True,
                 attach_cores=flat.cores_per_node),
    ])
    assert explicit.hierarchy.describe() \
        == default_hierarchy(flat).describe()
    jobs, placement = _random_workload(rng, flat, 4)
    if not jobs:
        return
    p2 = Placement(explicit)
    for jid, cores in placement.assignments.items():
        p2.assign(jid, cores)
    for be in ("loop", "segmented", "jax"):
        a = simulate(jobs, placement, flat, backend=be)
        b = simulate(jobs, p2, explicit, backend=be)
        _assert_close(b.total_wait, a.total_wait, 1e-9, f"{be} total_wait")
        _assert_close(b.workload_finish, a.workload_finish, 1e-9,
                      f"{be} workload_finish")
        _assert_close(b.max_server_utilisation, a.max_server_utilisation,
                      1e-9, f"{be} util")
        for jid in a.job_finish:
            _assert_close(b.job_finish[jid], a.job_finish[jid], 1e-9,
                          f"{be} job_finish[{jid}]")


def test_backends_agree_pallas_smoke():
    """One deterministic workload through the Pallas kernel (float32)."""
    rng = np.random.default_rng(7)
    cluster = ClusterTopology(n_nodes=4)
    jobs, placement = _random_workload(rng, cluster, 4)
    _check_all_backends(jobs, placement, cluster, backends=("pallas",))


def test_tie_phase_keys_on_job_and_rank():
    """Identical ranks in different jobs must NOT collide (the old bug)."""
    ranks = np.arange(64)
    p0 = tie_phase(0, ranks)
    p1 = tie_phase(1, ranks)
    assert not np.any(p0 == p1)
    # scalar and vector forms agree
    assert float(tie_phase(3, 5)) == float(tie_phase(3, np.array([5]))[0])


def test_same_rank_different_jobs_not_simultaneous():
    """Two identical jobs on symmetric cores: their senders' emissions
    must not tick at identical instants (phase keyed on job AND rank)."""
    j0 = AppGraph.from_pattern("a", "linear", 2, 64 * KB, 10.0, 5, job_id=0)
    j1 = AppGraph.from_pattern("b", "linear", 2, 64 * KB, 10.0, 5, job_id=1)
    e0 = j0.flat_messages().emit
    e1 = j1.flat_messages().emit
    assert not np.any(np.isin(e0, e1))


def test_flat_messages_cached_and_matches_loop_expansion():
    job = AppGraph.from_pattern("j", "all_to_all", 6, 64 * KB, 25.0, 9,
                                job_id=3)
    fm1 = job.flat_messages(0.5)
    fm2 = job.flat_messages(0.5)
    assert fm1 is fm2                      # cached per count_scale
    assert job.flat_messages(1.0) is not fm1
    # expansion matches the loop backend's per-pair python expansion
    src, dst = np.nonzero(job.cnt)
    n_expected = sum(max(1, int(round(job.cnt[i, j] * 0.5)))
                     for i, j in zip(src, dst))
    assert fm1.n_messages == n_expected
    assert fm1.n_pairs == src.size
    k = 0
    for i, j in zip(src, dst):
        n = max(1, int(round(job.cnt[i, j] * 0.5)))
        t = float(tie_phase(job.job_id, int(i))) \
            + np.arange(n) * (1.0 / job.lam[i, j])
        np.testing.assert_array_equal(fm1.emit[k:k + n], t)
        assert (fm1.src[k:k + n] == i).all()
        assert (fm1.dst[k:k + n] == j).all()
        k += n


@settings(max_examples=6, deadline=None)
@given(st.integers(0, 10_000), st.integers(2, 5))
def test_simulate_batch_matches_individual(seed, k):
    rng = np.random.default_rng(seed)
    cluster = ClusterTopology(n_nodes=4)
    jobs, placement = _random_workload(rng, cluster, 4)
    if not jobs:
        return
    trials = []
    for i in range(k):
        p = placement.copy()
        jid = jobs[i % len(jobs)].job_id
        cores = p.assignments[jid].copy()
        rng.shuffle(cores)
        p.assign(jid, cores)
        trials.append(p)
    for be in ("segmented", "jax"):
        batched = simulate_batch(jobs, trials, cluster, backend=be)
        for res, p in zip(batched, trials):
            ref = simulate(jobs, p, cluster, backend="loop")
            _assert_close(res.total_wait, ref.total_wait, TOL[be],
                          f"batch[{be}] total_wait")
            _assert_close(res.workload_finish, ref.workload_finish,
                          TOL[be], f"batch[{be}] workload_finish")


def test_simulate_batch_pallas_smoke():
    """K trial placements through the batched Pallas kernel (f32 rows)."""
    rng = np.random.default_rng(11)
    cluster = ClusterTopology(n_nodes=4)
    jobs, placement = _random_workload(rng, cluster, 4)
    trials = []
    for i in range(3):
        p = placement.copy()
        jid = jobs[i % len(jobs)].job_id
        cores = p.assignments[jid].copy()
        rng.shuffle(cores)
        p.assign(jid, cores)
        trials.append(p)
    for res, p in zip(simulate_batch(jobs, trials, cluster,
                                     backend="pallas"), trials):
        ref = simulate(jobs, p, cluster, backend="loop")
        _assert_close(res.total_wait, ref.total_wait, TOL["pallas"],
                      "batch[pallas] total_wait")


@pytest.mark.parametrize("backend", ["pallas", "jax"])
def test_lindley_scan_rows_ragged(backend):
    """Ragged level/stage rows pad with the max-plus identity and match a
    scalar f64 Lindley reference per row; the 3000-element row spans the
    kernel's 128-lane tile rows and runs down the rows of a block, with
    segment heads inside it. f32 ``pallas`` holds 1e-5 absolute on
    these O(10) waits; f64 ``jax`` holds 1e-12 relative."""
    from repro.core.sim_scan import _device_waits, _stack_rows
    rng = np.random.default_rng(0)
    rows = []
    for n in (5, 17, 3, 64):
        u = rng.uniform(-1, 1, n).astype(np.float32)
        u[0] = -np.inf                    # segment head: W_0 = 0
        rows.append((u, np.zeros(n, np.float32)))
    rng = np.random.default_rng(1)
    u = rng.uniform(-1, 1, 3000).astype(np.float32)
    u[0] = -np.inf
    u[rng.random(3000) < 0.01] = -np.inf
    rows.append((u, np.zeros(3000, np.float32)))
    tol = {"pallas": dict(atol=1e-5), "jax": dict(rtol=1e-12, atol=1e-12)}
    for (u, v), w in zip(rows, _device_waits(*_stack_rows(rows), backend)):
        w = w[:u.size]
        cur, ref = 0.0, []
        for i in range(len(u)):
            cur = max(cur + float(u[i]), float(v[i])) if i else 0.0
            ref.append(cur)
        np.testing.assert_allclose(w, ref, **tol[backend])


def test_lindley_scan_carry_across_blocks():
    """Rows longer than one (BLOCK_ROWS, 128) block: the VMEM carry
    composes across grid steps, and the ragged tail pads to a block."""
    from repro.core.sim_scan import _device_waits
    from repro.kernels.lindley_scan import BLOCK_ROWS, LANES, lindley_scan
    rng = np.random.default_rng(3)
    u = rng.uniform(-1, 1, (2, 2 * BLOCK_ROWS * LANES + 5000))
    u[:, 0] = -np.inf                     # one queue per row, one head
    v = np.zeros_like(u)
    got = np.asarray(lindley_scan(u, v, interpret=True))
    np.testing.assert_allclose(got, _device_waits(u, v, "jax"),
                               rtol=1e-4, atol=1e-3)


def test_jax_backend_is_f64():
    """The ``jax`` scan runs in float64 (the 1e-9 contract's abs tolerance
    would hide an f32 scan on ~1e-3 s waits): absolute arrival times near
    1e4 s with microsecond services, near saturation, agree with numpy
    ``segmented`` to 1e-12 relative — f32 misses by ~1e-7."""
    import jax
    from repro.core.sim_scan import (_jax_scan_fn, _pack, _pass_waits,
                                     _segment_starts, _u_elements)
    rng = np.random.default_rng(5)
    sid = np.repeat(np.arange(8), 512)            # 4096: no padding
    arr = np.concatenate([1e4 + k + np.cumsum(rng.exponential(1e-6, 512))
                          for k in range(8)])
    srv = rng.uniform(0.5e-6, 1.4e-6, arr.size)
    starts = _segment_starts(sid)
    with jax.enable_x64(True):                    # every chunk is real
        out = _jax_scan_fn()(*_pack([_u_elements(arr, srv, starts)], "jax"))
    assert {c.dtype for c in out} == {np.dtype(np.float64)}
    want = _pass_waits(arr, srv, starts, "segmented")
    got = _pass_waits(arr, srv, starts, "jax")
    assert want.max() > 1e-6                       # queues really build
    np.testing.assert_allclose(got, want, rtol=1e-12, atol=1e-18)


# Rows up to 2^13 elements cross the jax link in 1024-wide chunks (the
# padded width over min(8, padded width / 1024) chunks): these lengths
# leave 1, 2, ..., 8 chunks sent, at a chunk's end and one past it.
_LINK_CHUNK = 1024
_LINK_LENGTHS = [k * _LINK_CHUNK + d for k in range(1, 9) for d in (0, 1)][:-1]


def _queue_pass(rng, n):
    """One sorted multi-server round of ``n`` messages near saturation:
    (arrivals, services, segment heads), four servers."""
    from repro.core.sim_scan import _segment_starts
    sid = np.sort(rng.integers(0, 4, n))
    sid[0] = 0
    arr = rng.uniform(0.0, n / 4, n)
    arr = arr[np.lexsort((arr, sid))]
    return arr, rng.uniform(0.5, 1.4, n), _segment_starts(sid)


def _lindley_loop(arr, srv, starts):
    """Scalar f64 Lindley waits, server by server in sorted order."""
    w = np.empty(arr.size)
    for i in range(arr.size):
        w[i] = 0.0 if starts[i] else max(
            0.0, w[i - 1] + srv[i - 1] - (arr[i] - arr[i - 1]))
    return w


@pytest.mark.parametrize("n_rows", [1, 4])
@pytest.mark.parametrize("n", _LINK_LENGTHS)
def test_jax_link_chunks_keep_every_wait(n, n_rows):
    """Only the chunks holding real elements cross the link, and no ``v``
    row: the waits equal, bit for bit, the padded ``(u, v)`` scan with
    every chunk sent, match a scalar Lindley loop to 1e-12, and the
    ``sim.device`` span counts 8 bytes per element of the chunks sent.
    One row goes through ``_pass_waits`` (built straight into the link's
    chunks); four ragged rows, the longest ``n``, through ``_pack`` as a
    batched stage does."""
    import jax
    import jax.numpy as jnp
    from repro import obs
    from repro.core.sim_scan import (_blocked_scan, _device_scan, _pack,
                                     _pad, _pass_waits, _stack_rows,
                                     _u_elements)
    rng = np.random.default_rng(n * 10 + n_rows)
    lengths = [n, -(-n // 2), n // 3 + 1, 5][:n_rows]
    passes = [_queue_pass(rng, m) for m in lengths]
    rows = [_u_elements(*p) for p in passes]
    t0 = time.perf_counter()
    with obs.recording() as rec:
        if n_rows == 1:
            got = _pass_waits(*passes[0], "jax")[None]
        else:
            got = _device_scan(_pack(rows, "jax"), n, "jax")
    spans = obs.host_summary(t0, time.perf_counter())
    npad = max(_LINK_CHUNK, 1 << (n - 1).bit_length())
    sent = -(-n // _LINK_CHUNK)
    assert spans["sim.device"].count == 8 * n_rows * sent * _LINK_CHUNK
    counters = {k: rec.metrics.counter("sim.device." + k).total
                for k in ("chunks_sent", "chunks_total", "d2h_bytes")}
    assert counters == {"chunks_sent": sent,
                        "chunks_total": npad // _LINK_CHUNK,
                        "d2h_bytes": 8 * n_rows * sent * _LINK_CHUNK}
    u, v = _pad(*_stack_rows([(r, np.zeros(r.size)) for r in rows]))
    with jax.enable_x64(True):
        padded = np.asarray(jax.jit(
            lambda u, v: jnp.maximum(*_blocked_scan(u, v)))(u, v))
    for k, (m, p) in enumerate(zip(lengths, passes)):
        np.testing.assert_array_equal(got[k, :m], padded[k, :m])
        np.testing.assert_allclose(got[k, :m], _lindley_loop(*p),
                                   rtol=1e-12, atol=1e-12)


def test_jax_warm_shape_compiles_nothing_at_any_chunk_count():
    """Warmed as the benchmark warms (``_device_waits`` of zeros and
    ``v = -inf`` at ``(rows, npad)``), calls that send any number of that
    width's chunks build no program: the window compiles nothing."""
    import jax
    from repro.core.sim_scan import (_device_scan, _device_waits,
                                     _jax_scan_fn, _pack, _pass_waits,
                                     _u_elements)
    npad = 8 * _LINK_CHUNK
    _jax_scan_fn().clear_cache()     # what earlier tests built is not warm
    for b in (1, 4):
        _device_waits(np.zeros((b, npad)), np.full((b, npad), -np.inf), "jax")
    built = []

    def on_compile(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            built.append(event)

    rng = np.random.default_rng(7)
    jax.monitoring.register_event_duration_secs_listener(on_compile)
    try:
        for n in (m for m in _LINK_LENGTHS if m > npad // 2):
            _pass_waits(*_queue_pass(rng, n), "jax")
            rows = [_u_elements(*_queue_pass(rng, m))
                    for m in (n, n // 2, 3, n - 1)]
            _device_scan(_pack(rows, "jax"), n, "jax")
    finally:
        jax.monitoring.unregister_event_duration_listener(on_compile)
    assert built == []


def test_order_by_server_arrival_repairs_ties_to_original_order():
    """Equal (server, arrival) runs must order by original index — the
    loop backend's lexsort semantics — despite the unstable first pass."""
    from repro.core.sim_scan import _order_by_server_arrival
    rng = np.random.default_rng(0)
    n = 4000
    sid = rng.integers(0, 4, n)
    arrival = rng.integers(0, 8, n).astype(np.float64)   # many exact ties
    got = _order_by_server_arrival(sid, arrival)
    want = np.lexsort((arrival, sid))
    np.testing.assert_array_equal(got, want)


def test_scan_tie_repair_matches_loop_on_colliding_phases():
    """Jobs built to EMIT at identical instants (same job_id -> same
    phases) exercise the in-scan tie repair against the loop backend."""
    L = np.zeros((6, 6))
    lam = np.zeros((6, 6))
    cnt = np.zeros((6, 6), dtype=np.int64)
    for i, j in ((0, 3), (1, 4), (2, 5)):       # 3 senders, 1 receiver node
        L[i, j] = 1 * MB
        lam[i, j] = 50.0
        cnt[i, j] = 20
    cluster = ClusterTopology(n_nodes=4)
    # same job_id twice is invalid in one Placement; instead craft one job
    # whose senders share a phase by construction: same rank emits to two
    # receivers at identical instants through the SAME NIC
    L[0, 4] = 2 * MB
    lam[0, 4] = 50.0
    cnt[0, 4] = 20
    job = AppGraph("tie", L, lam, cnt, job_id=0)
    placement = Placement(cluster)
    placement.assign(0, np.array([0, 1, 2, 16, 32, 48]))
    _check_all_backends([job], placement, cluster)


def test_scan_r2_tie_repair_cross_job_collision():
    """Two jobs whose phases collide exactly (job_id 104729 wraps the
    phase modulus) send equal-size messages from different TX nodes to one
    RX node: their RX arrivals tie EXACTLY and the waits {0, s} land on
    one job or the other depending on tie order — the scan backends must
    attribute them the way the loop backend's stable sort does."""
    assert float(tie_phase(0, 0)) == float(tie_phase(104729, 0))
    cluster = ClusterTopology(n_nodes=4)
    jobs, placement = [], Placement(cluster)
    # job 0 sends from the HIGHER tx node so the scan's r1-domain order
    # disagrees with flattening order on the tied RX arrivals — the
    # repair must restore flattening order or per-job waits come out wrong
    for jid, (s_core, r_core) in ((0, (16, 32)), (104729, (0, 33))):
        job = AppGraph.from_pattern(f"j{jid}", "linear", 2, 64 * KB, 10.0,
                                    15, job_id=jid)
        placement.assign(jid, np.array([s_core, r_core]))
        jobs.append(job)
    base = _check_all_backends(jobs, placement, cluster)
    assert base.total_wait > 0.0          # ties queued at the shared RX


def test_resolve_backend(monkeypatch):
    import jax
    assert resolve_backend("loop") == "loop"
    monkeypatch.delenv("REPRO_SIM_BACKEND", raising=False)
    assert resolve_backend("auto") == (
        "segmented" if jax.default_backend() == "cpu" else "jax")
    assert resolve_backend(None) in BACKENDS
    with pytest.raises(KeyError):
        resolve_backend("omnetpp")


def test_empty_workload_all_backends():
    cluster = ClusterTopology(n_nodes=2)
    for be in ("loop", "segmented", "jax"):
        res = simulate([], Placement(cluster), cluster, backend=be)
        assert res.total_wait == 0.0 and res.n_messages == 0


# ---------------------------------------------------------------------------
# Delta-aware workload assembly (the scheduler's warm-start path)
# ---------------------------------------------------------------------------
_FLAT_FIELDS = ("emit", "pair_of", "job_row", "pair_src", "pair_dst",
                "pair_size", "time_order", "emit_t", "pair_of_t",
                "job_starts", "job_msgs", "job_pairs", "job_procs")


def _assert_flat_equal(flat, jobs, count_scale):
    """Delta-assembled flat must be BIT-equal to a cold full rebuild."""
    from repro.core.sim_scan import _WorkloadFlat
    ref = _WorkloadFlat(jobs, count_scale)
    for f in _FLAT_FIELDS:
        assert np.array_equal(getattr(flat, f), getattr(ref, f)), f
    assert flat.offsets == ref.offsets and flat.n_procs == ref.n_procs


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 10_000))
def test_delta_flat_matches_full_rebuild(seed):
    """Random add/remove churn through the delta constructors stays
    bit-identical to rebuilding the concatenated workload from scratch
    (including the stable arrival-time sort order)."""
    from repro.core.sim_scan import _WorkloadFlat, flatten_delta
    rng = np.random.default_rng(seed)
    cluster = ClusterTopology(n_nodes=6)
    jobs, _ = _random_workload(rng, cluster, 6)
    if len(jobs) < 3:
        return
    cs = float(rng.choice([0.5, 1.0]))
    flat = _WorkloadFlat(jobs, cs)
    live = list(jobs)
    next_id = 100
    for _ in range(6):
        if live and rng.random() < 0.5:
            victim = live.pop(int(rng.integers(0, len(live))))
            flat = flat.with_job_removed(victim.job_id)
        else:
            pattern = PATTERNS[int(rng.integers(0, len(PATTERNS)))]
            job = AppGraph.from_pattern(f"d{next_id}", pattern,
                                        int(rng.integers(2, 7)), 64 * KB,
                                        50.0, int(rng.integers(1, 25)),
                                        job_id=next_id)
            next_id += 1
            live.append(job)
            flat = flat.with_job_added(job)
        _assert_flat_equal(flat, live, cs)
    # flatten_delta applies the same steps from a cached predecessor
    if len(live) >= 2:
        churned = live[1:] + [AppGraph.from_pattern(
            "tail", PATTERNS[0], 4, 64 * KB, 50.0, 10, job_id=next_id)]
        flat2 = flatten_delta(churned, cs, prev=flat)
        _assert_flat_equal(flat2, churned, cs)


@settings(max_examples=6, deadline=None)
@given(st.integers(0, 10_000))
def test_sim_handle_matches_cold_simulate_under_churn(seed):
    """SimHandle's warm re-simulation over a churning live set must agree
    with the loop reference at every step (1e-9, the f64 contract)."""
    from repro.core.simulator import SimHandle
    rng = np.random.default_rng(seed)
    cluster = ClusterTopology(n_nodes=4)
    handle = SimHandle(cluster, count_scale=1.0, backend="segmented")
    live, next_id = [], 0
    for _ in range(10):
        if live and rng.random() < 0.4:
            live.pop(int(rng.integers(0, len(live))))
        else:
            pattern = PATTERNS[int(rng.integers(0, len(PATTERNS)))]
            live.append(AppGraph.from_pattern(
                f"h{next_id}", pattern, int(rng.integers(2, 7)), 64 * KB,
                50.0, int(rng.integers(1, 25)), job_id=next_id))
            next_id += 1
        if not live:
            continue
        if sum(j.n_procs for j in live) > cluster.n_cores:
            live.pop()
            continue
        placement = Placement(cluster)
        off = 0
        for job in live:
            placement.assign(job.job_id, np.arange(off, off + job.n_procs))
            off += job.n_procs
        warm = handle.simulate(live, placement)
        ref = simulate(live, placement, cluster, 1.0, backend="loop")
        _assert_close(warm.total_wait, ref.total_wait, 1e-9, "total_wait")
        _assert_close(warm.max_server_utilisation,
                      ref.max_server_utilisation, 1e-6, "util")
        assert warm.job_finish.keys() == ref.job_finish.keys()
        for jid in ref.job_finish:
            _assert_close(warm.job_finish[jid], ref.job_finish[jid], 1e-9,
                          f"job_finish[{jid}]")
