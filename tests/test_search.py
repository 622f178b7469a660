"""Property tests for the batched placement search (DESIGN.md §10).

The subsystem's three contracts:

- **Never worse than the seed**: the search's returned placement scores
  at least as well as its named seed strategy on the simulated objective
  (greedy and annealing, any seed, fragmented or empty tracker).
- **Accounting**: every emitted placement passes ``Placement`` validity,
  stays inside the free pool it was given, and the strategy adapters
  leave the caller's ``FreeCoreTracker`` claiming exactly the winning
  cores. Neighbour moves (swap / migrate / subtree) preserve these
  invariants state by state.
- **Determinism**: a fixed PRNG seed yields a bit-identical trajectory
  (and final placement) on every simulator backend — scores are
  quantized before comparison, so sub-tolerance backend noise cannot
  flip an accept decision.
"""
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # pinned image lacks hypothesis — deterministic fallback
    from repro.testing import given, settings, strategies as st

from repro.core.graphs import AppGraph, ClusterTopology, FreeCoreTracker
from repro.core.mapping import ONE_SHOT_STRATEGIES, STRATEGIES
from repro.sched import (FleetScheduler, RemapConfig, SchedulerConfig,
                         get_trace, resolve_strategy)
from repro.search import (SearchState, domain_sizes, neighbours,
                          objective_of, search_placement, search_strategy,
                          search_strategy_result)

BACKENDS = ["loop", "segmented", "jax"]


def small_cluster() -> ClusterTopology:
    return ClusterTopology(n_nodes=8, sockets_per_node=2, cores_per_socket=2)


def small_jobs(rng: np.random.Generator, n_jobs: int = 3) -> list:
    patterns = ("all_to_all", "bcast_scatter", "gather_reduce", "linear")
    jobs = []
    for j in range(n_jobs):
        jobs.append(AppGraph.from_pattern(
            name=f"j{j}", pattern=patterns[int(rng.integers(len(patterns)))],
            n_procs=int(rng.integers(4, 9)),
            length=float(rng.choice([64 << 10, 2 << 20])),
            rate=10.0, count=40, job_id=j))
    return jobs


def occupied_tracker(rng, cluster, jobs) -> FreeCoreTracker:
    """Fragmented tracker with enough head-room left for the jobs."""
    tracker = FreeCoreTracker(cluster)
    need = sum(j.n_procs for j in jobs)
    spare = cluster.n_cores - need
    n_occupy = int(rng.integers(0, max(spare // 2, 1)))
    occupy = rng.choice(cluster.n_cores, size=n_occupy, replace=False)
    if n_occupy:
        tracker.take_cores(occupy)
    return tracker


# ---------------------------------------------------------------------------
# never worse than the seed
# ---------------------------------------------------------------------------
@settings(max_examples=5, deadline=None)
@given(st.integers(0, 10_000), st.sampled_from(ONE_SHOT_STRATEGIES),
       st.booleans())
def test_never_worse_than_seed(seed_int, seed_strategy, anneal):
    rng = np.random.default_rng(seed_int)
    cluster = small_cluster()
    jobs = small_jobs(rng)
    tracker = occupied_tracker(rng, cluster, jobs)
    base = tracker.used.copy()
    res = search_placement(jobs, cluster, tracker, seed=seed_strategy,
                           anneal=anneal, budget=48, population=8,
                           rng_seed=seed_int)
    assert res.objective <= res.seed_objective
    # the reported seed objective is the honest score of the seed placement
    seed_tracker = FreeCoreTracker(cluster, occupied=base)
    seed_pl = STRATEGIES[seed_strategy](jobs, cluster, seed_tracker)
    assert res.seed_objective == objective_of(
        jobs, seed_pl, cluster, objective_scale=res.objective_scale)
    # multi-seed portfolio: never worse than ANY one-shot that fits
    for name, score in res.seeds_scored.items():
        assert res.objective <= score, name
    assert res.evaluations <= 48


# ---------------------------------------------------------------------------
# placement validity + tracker accounting
# ---------------------------------------------------------------------------
@settings(max_examples=5, deadline=None)
@given(st.integers(0, 10_000))
def test_strategy_claims_exactly_the_winning_cores(seed_int):
    rng = np.random.default_rng(seed_int)
    cluster = small_cluster()
    jobs = small_jobs(rng)
    tracker = occupied_tracker(rng, cluster, jobs)
    base = tracker.used.copy()
    pl = search_strategy(jobs, cluster, tracker, seed="new", budget=40,
                         population=8, rng_seed=seed_int)
    pl.validate()
    placed = np.zeros(cluster.n_cores, dtype=bool)
    for job in jobs:
        cores = pl.assignments[job.job_id]
        assert cores.size == job.n_procs
        assert not base[cores].any(), "search escaped its free pool"
        placed[cores] = True
    assert np.array_equal(tracker.used, base | placed)
    # conservation: nothing leaked, nothing double-counted
    assert tracker.total_free() == cluster.n_cores - int((base | placed).sum())


@settings(max_examples=5, deadline=None)
@given(st.integers(0, 10_000), st.integers(1, 40))
def test_moves_preserve_state_invariants(seed_int, n_moves):
    rng = np.random.default_rng(seed_int)
    cluster = small_cluster()
    jobs = small_jobs(rng)
    tracker = occupied_tracker(rng, cluster, jobs)
    base = tracker.used.copy()
    pl = STRATEGIES["new"](jobs, cluster, tracker)
    state = SearchState.from_placement(cluster, pl, ~base)
    sizes = domain_sizes(cluster)
    for move, nxt in neighbours(rng, state, n_moves, sizes=sizes):
        nxt.placement().validate()
        occupied = np.zeros(cluster.n_cores, dtype=bool)
        for job in jobs:
            cores = nxt.assignments[job.job_id]
            assert cores.size == job.n_procs, move
            assert not base[cores].any(), move
            occupied[cores] = True
        # free mask stays the exact complement of (pre-occupied | placed)
        assert np.array_equal(nxt.free, ~(base | occupied)), move
        state = nxt                      # walk on from the mutated state


# ---------------------------------------------------------------------------
# determinism across backends
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("anneal", [False, True])
def test_trajectory_bit_identical_across_backends(anneal):
    rng = np.random.default_rng(7)
    cluster = small_cluster()
    jobs = small_jobs(rng, n_jobs=4)
    runs = {}
    for backend in BACKENDS:
        runs[backend] = search_placement(
            jobs, cluster, seed="new", anneal=anneal, budget=64,
            population=8, rng_seed=123, backend=backend)
    ref = runs[BACKENDS[0]]
    for backend, res in runs.items():
        assert res.trajectory == ref.trajectory, backend
        assert res.objective == ref.objective, backend
        assert res.evaluations == ref.evaluations, backend
        for jid, cores in ref.placement.assignments.items():
            assert np.array_equal(res.placement.assignments[jid], cores), \
                (backend, jid)


def test_same_seed_same_result_repeated():
    rng = np.random.default_rng(11)
    cluster = small_cluster()
    jobs = small_jobs(rng)
    a = search_placement(jobs, cluster, seed="cyclic", budget=48, rng_seed=5)
    b = search_placement(jobs, cluster, seed="cyclic", budget=48, rng_seed=5)
    assert a.trajectory == b.trajectory
    assert a.objective == b.objective


# ---------------------------------------------------------------------------
# registry + scheduler integration
# ---------------------------------------------------------------------------
def test_registry_and_resolve():
    for seed in ONE_SHOT_STRATEGIES:
        assert f"search:{seed}" in STRATEGIES
    assert "anneal" in STRATEGIES
    assert resolve_strategy("search:new") is STRATEGIES["search:new"]
    assert resolve_strategy("search:new_tpu") is not None
    with pytest.raises(ValueError):
        # a search strategy cannot seed itself (no recursion)
        search_placement([], small_cluster(), seed="search:new")
    with pytest.raises(KeyError):
        search_placement([], small_cluster(), seed="no_such_strategy")


def test_scheduler_admission_with_search_strategy():
    from repro.core.mapping import make_search_strategy

    spec = get_trace("table4_poisson", n_arrivals=6)
    sched = FleetScheduler(
        spec.cluster, make_search_strategy("new", budget=24, population=8),
        config=SchedulerConfig(
            remap=RemapConfig(interval=5.0),
            count_scale=spec.count_scale,
            state_bytes_per_proc=spec.state_bytes_per_proc))
    sched.submit_trace(spec.arrivals)
    stats = sched.run()
    sched.check_invariants()
    assert stats.n_jobs == 6
    assert all(j["departure"] is not None for j in stats.per_job.values())


def test_scheduler_remap_budget_search():
    def run():
        spec = get_trace("rack_oversub", n_arrivals=8)
        sched = FleetScheduler(
            spec.cluster, "new", config=SchedulerConfig(
                remap=RemapConfig(interval=5.0, budget=48, population=8,
                                  rng_seed=3),
                count_scale=spec.count_scale,
                state_bytes_per_proc=spec.state_bytes_per_proc))
        sched.submit_trace(spec.arrivals)
        stats = sched.run()
        sched.check_invariants()
        return sched, stats

    sched_a, stats_a = run()
    sched_b, stats_b = run()
    # deterministic: identical trace + rng seed -> identical schedule
    assert stats_a.total_msg_wait == stats_b.total_msg_wait
    assert stats_a.makespan == stats_b.makespan
    assert stats_a.n_remap_commits == stats_b.n_remap_commits
    # commit bookkeeping is consistent with the decisions log
    commits = [d for d in sched_a.decisions if d.committed]
    assert stats_a.n_remap_commits == len(commits)
    assert stats_a.migrated_bytes == pytest.approx(
        sum(d.bytes_moved for d in commits))
    # every commit claimed a strictly positive projected gain
    assert all(d.wait_gain > d.migration_time for d in commits)


def test_remap_budget_never_exceeded():
    spec = get_trace("rack_oversub", n_arrivals=8)
    calls = []
    sched = FleetScheduler(
        spec.cluster, "new", config=SchedulerConfig(
            remap=RemapConfig(interval=5.0, budget=32, population=8),
            count_scale=spec.count_scale,
            state_bytes_per_proc=spec.state_bytes_per_proc))
    orig = sched._sim.simulate_batch

    def counting(jobs, placements):
        calls.append(len(placements))
        return orig(jobs, placements)

    sched._sim.simulate_batch = counting
    orig_pass = sched._remap_search

    def budgeted_pass(live, res):
        calls.clear()
        orig_pass(live, res)
        assert sum(calls) <= sched.remap_budget

    sched._remap_search = budgeted_pass
    sched.submit_trace(spec.arrivals)
    sched.run()
    sched.check_invariants()


# ---------------------------------------------------------------------------
# empty / degenerate inputs
# ---------------------------------------------------------------------------
def test_full_cluster_swaps_only():
    """On a 100%-occupied cluster only swaps exist — search still works."""
    cluster = ClusterTopology(n_nodes=2, sockets_per_node=2,
                              cores_per_socket=2)
    jobs = [AppGraph.from_pattern(name="a", pattern="all_to_all", n_procs=8,
                                  length=1 << 20, rate=10.0, count=40,
                                  job_id=0)]
    res = search_placement(jobs, cluster, seed="blocked", budget=32,
                           population=8, rng_seed=0)
    assert res.objective <= res.seed_objective
    assert set(res.placement.assignments[0].tolist()) == set(range(8))


def test_search_result_metadata():
    rng = np.random.default_rng(0)
    cluster = small_cluster()
    jobs = small_jobs(rng)
    res = search_strategy_result(jobs, cluster, seed="new", budget=40,
                                 rng_seed=2)
    assert res.seed_name == "new"
    assert res.accepted == len(res.trajectory)
    assert 0.0 <= res.objective_scale <= 1.0
    assert res.gain_vs_seed >= 0.0
