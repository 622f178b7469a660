"""Scan-based simulator backends: segmented Lindley passes, no server loop.

The ``loop`` backend (``simulator._simulate_loop``) runs Lindley's recursion
once per server in a Python loop. This module replaces that with ONE
segmented scan over all servers of a routing round at once (DESIGN.md §8):

* Sort messages by ``(server, arrival)`` (stable — ties keep flattening
  order, matching the loop backend sequence-for-sequence).
* Lindley's recursion ``W_n = max(0, W_{n-1} + X_n)`` with
  ``X_n = S_{n-1} - (A_n - A_{n-1})`` is max-plus linear: each message is
  the map ``w -> max(w + X_n, 0)``, segment heads are the constant map
  ``w -> 0``. Maps ``(u, v): w -> max(w + u, v)`` compose associatively:
  ``(u1, v1) . (u2, v2) = (u1 + u2, max(v1 + u2, v2))`` — so the whole
  multi-server pass is one associative scan with heads encoded as
  ``(-inf, 0)``; no per-segment bookkeeping at scan time.

Three implementations of the scan:

* ``segmented`` — numpy: segmented prefix sums plus a segmented running
  minimum computed densely per server row (``np.minimum.accumulate`` on a
  (servers, max-queue) grid; doubling-sweep fallback when the grid would
  blow up). Matches ``loop`` to ~1e-12 relative on stable loads; its
  prefix sums run across all servers, so under sustained overload a
  lightly loaded server's small waits cancel against large sums (4e-7
  relative on one job's wait in the full-count Table-4 snapshot, where
  ``jax`` holds 3e-11).
* ``jax``       — a blocked ``jax.lax.associative_scan`` over the ``(u, v)``
  elements, jitted, padded to powers of two to bound recompiles; float64
  under ``jax.enable_x64``. Batches over a leading axis for
  ``simulate_batch``. Only ``u`` crosses the host/device link, and only
  where it is real: every element's ``v`` is 0 and is made on the device;
  a padded row travels as up to ``_LINK_CHUNKS`` chunks, of which only
  those holding real elements are sent (a device-resident zero chunk
  fills the rest) and only those covering the real length come back.
* ``pallas``    — ``repro.kernels.lindley_scan``: the same elements through
  a blocked Pallas TPU kernel (float32; interpreted off a TPU).

Routing gives every message a *stage-0* server (cache / memory / its first
hierarchy hop — disjoint id spaces form the scan's per-level server axis)
and inter-node messages further stages along the ``NetworkHierarchy`` LCA
path (DESIGN.md §9): hierarchy hops merge greedily into multi-server
passes wherever no message takes two of them, so the default flat/TPU
configs still run as exactly two scans, and an L-level tree needs at most
2L regardless of cluster size. Each stage's arrivals are the previous
stage's departures (+ the LCA level's latency at the apex).

Per-workload host arrays (flattened messages, the arrival-time sort order)
are placement-independent; they are cached keyed on the live job set so the
scheduler's repeated ``simulate()`` calls only pay for routing + scanning.
"""
from __future__ import annotations

from collections import OrderedDict
from typing import Sequence

import numpy as np

from .. import obs
from .graphs import AppGraph, ClusterTopology, Placement
from .simulator import SimResult

_SPAN_FLOOR = 1e-30       # utilisation denominator floor (matches loop)
_DENSE_CUMMIN_CAP = 1 << 22   # max cells of the per-server min grid (32 MB)


def _count(name: str, v: float = 1) -> None:
    """Flat-assembly provenance counter on the installed recorder —
    distinguishes warm reuse / delta patches / cache hits / full builds."""
    rec = obs.current()
    if rec.enabled:
        rec.metrics.counter(name).inc(v)


# ---------------------------------------------------------------------------
# Workload flattening (placement-independent, cached per live job set)
# ---------------------------------------------------------------------------
class _WorkloadFlat:
    """Concatenated flat messages of one job set + arrival-time sort order.

    Pair-granular fields (``pair_*``) drive routing — there are orders of
    magnitude fewer communicating pairs than messages; ``pair_of`` expands
    pair-level results to messages with one gather.

    Two construction paths: the full build below, and the delta paths
    :meth:`with_job_added` / :meth:`with_job_removed` used by the
    scheduler's warm-start handle (``simulator.SimHandle``) — on a live
    fleet the job set changes by one job per event, so the concatenated
    arrays and the sorted time order are patched in O(M) (a block splice
    plus a sorted merge) instead of rebuilt with a fresh O(M log M)
    argsort.
    """

    def __init__(self, jobs: Sequence[AppGraph], count_scale: float):
        self.jobs = list(jobs)            # strong refs keep id() keys valid
        self.count_scale = count_scale
        job_rows, pair_ofs, emits = [], [], []
        p_src, p_dst, p_size = [], [], []
        msgs, pairs, procs = [], [], []
        proc_off = 0
        pair_off = 0
        for k, job in enumerate(jobs):
            fm = job.flat_messages(count_scale)
            msgs.append(fm.n_messages)
            pairs.append(fm.n_pairs)
            procs.append(job.n_procs)
            if fm.n_messages:
                job_rows.append(np.full(fm.n_messages, k, dtype=np.int32))
                pair_ofs.append(fm.pair_of.astype(np.int64) + pair_off)
                emits.append(fm.emit)
                p_src.append(fm.pair_src.astype(np.int64) + proc_off)
                p_dst.append(fm.pair_dst.astype(np.int64) + proc_off)
                p_size.append(fm.pair_size)
            proc_off += job.n_procs
            pair_off += fm.n_pairs
        self._set_blocks(msgs, pairs, procs)
        if emits:
            self.job_row = np.concatenate(job_rows)
            self.pair_of = np.concatenate(pair_ofs).astype(np.int32)
            self.emit = np.concatenate(emits)
            self.pair_src = np.concatenate(p_src)
            self.pair_dst = np.concatenate(p_dst)
            self.pair_size = np.concatenate(p_size)
            # stable time order: the placement-independent half of the
            # stable (server, arrival) sort every round-1 pass needs —
            # cached pre-permuted views keep per-call gathers narrow
            self.time_order = np.argsort(self.emit,
                                         kind="stable").astype(np.int32)
        else:
            # same field shape as the populated case so the delta paths
            # (and their differential tests) work from an empty flat too
            self.job_row = np.empty(0, dtype=np.int32)
            self.pair_of = np.empty(0, dtype=np.int32)
            self.emit = np.empty(0)
            self.pair_src = np.empty(0, dtype=np.int64)
            self.pair_dst = np.empty(0, dtype=np.int64)
            self.pair_size = np.empty(0)
            self.time_order = np.empty(0, dtype=np.int32)
        self._set_time_views()

    # -- shared finalisation ------------------------------------------------
    def _set_blocks(self, msgs, pairs, procs) -> None:
        """Per-job block sizes (messages / pairs / procs) + derived offsets."""
        self.job_msgs = np.asarray(msgs, dtype=np.int64)
        self.job_pairs = np.asarray(pairs, dtype=np.int64)
        self.job_procs = np.asarray(procs, dtype=np.int64)
        self.job_starts = np.concatenate(
            [[0], np.cumsum(self.job_msgs)[:-1]]).astype(np.int64)
        self.job_nonempty = self.job_msgs > 0
        self.offsets = {}
        off = 0
        for job, p in zip(self.jobs, self.job_procs):
            self.offsets[job.job_id] = off
            off += int(p)
        self.n_procs = off

    def _set_time_views(self) -> None:
        self.emit_t = self.emit[self.time_order]
        self.pair_of_t = self.pair_of[self.time_order]

    @property
    def n_messages(self) -> int:
        return int(self.emit.size)

    # -- delta construction (the scheduler's churn pattern) ------------------
    def with_job_added(self, job: AppGraph) -> "_WorkloadFlat":
        """New flat with ``job`` appended, reusing this flat's arrays.

        The job's cached block (``AppGraph.flat_messages``) is spliced on
        and its cached sorted order merged into ``time_order`` with one
        ``searchsorted`` — equal emit times keep stable-argsort semantics
        (old messages first, block order within the new job).
        """
        fm = job.flat_messages(self.count_scale)
        new = object.__new__(_WorkloadFlat)
        new.jobs = self.jobs + [job]
        new.count_scale = self.count_scale
        k = len(self.jobs)
        pair_off = int(self.pair_size.size)
        proc_off = self.n_procs
        if fm.n_messages:
            new.job_row = np.concatenate(
                [self.job_row, np.full(fm.n_messages, k, dtype=np.int32)])
            new.pair_of = np.concatenate(
                [self.pair_of,
                 (fm.pair_of.astype(np.int64) + pair_off).astype(np.int32)])
            new.emit = np.concatenate([self.emit, fm.emit])
            new.pair_src = np.concatenate(
                [self.pair_src, fm.pair_src.astype(np.int64) + proc_off])
            new.pair_dst = np.concatenate(
                [self.pair_dst, fm.pair_dst.astype(np.int64) + proc_off])
            new.pair_size = np.concatenate([self.pair_size, fm.pair_size])
            blk = fm.time_order
            blk_emit = fm.emit[blk]
            # merge two sorted runs; 'right' keeps ties stable (old first)
            at = np.searchsorted(self.emit_t, blk_emit, side="right")
            pos = at + np.arange(blk.size)
            order = np.empty(self.n_messages + blk.size, dtype=np.int32)
            mask = np.ones(order.size, dtype=bool)
            mask[pos] = False
            order[mask] = self.time_order
            order[pos] = blk + np.int32(self.n_messages)
            new.time_order = order
        else:
            new.job_row = self.job_row
            new.pair_of = self.pair_of
            new.emit = self.emit
            new.pair_src = self.pair_src
            new.pair_dst = self.pair_dst
            new.pair_size = self.pair_size
            new.time_order = self.time_order
        new._set_blocks(np.append(self.job_msgs, fm.n_messages),
                        np.append(self.job_pairs, fm.n_pairs),
                        np.append(self.job_procs, job.n_procs))
        new._set_time_views()
        return new

    def with_job_removed(self, job_id: int) -> "_WorkloadFlat":
        """New flat with ``job_id``'s block spliced out, arrays reused.

        Message/pair/proc indices of later jobs shift down by the removed
        block's sizes; ``time_order`` drops the block's entries and
        renumbers the survivors — all O(M) vector ops, no re-sort.
        """
        k = next(i for i, j in enumerate(self.jobs) if j.job_id == job_id)
        m0 = int(self.job_starts[k])
        m1 = m0 + int(self.job_msgs[k])
        p0 = int(self.job_pairs[:k].sum())
        p1 = p0 + int(self.job_pairs[k])
        nm, npair, nproc = m1 - m0, p1 - p0, int(self.job_procs[k])
        new = object.__new__(_WorkloadFlat)
        new.jobs = self.jobs[:k] + self.jobs[k + 1:]
        new.count_scale = self.count_scale
        new.job_row = np.concatenate(
            [self.job_row[:m0], self.job_row[m1:] - np.int32(1)])
        new.pair_of = np.concatenate(
            [self.pair_of[:m0], self.pair_of[m1:] - np.int32(npair)])
        new.emit = np.concatenate([self.emit[:m0], self.emit[m1:]])
        new.pair_src = np.concatenate(
            [self.pair_src[:p0], self.pair_src[p1:] - nproc])
        new.pair_dst = np.concatenate(
            [self.pair_dst[:p0], self.pair_dst[p1:] - nproc])
        new.pair_size = np.concatenate(
            [self.pair_size[:p0], self.pair_size[p1:]])
        keep = self.time_order < m0
        keep |= self.time_order >= m1
        order = self.time_order[keep].copy()
        order[order >= m1] -= np.int32(nm)
        new.time_order = order
        new._set_blocks(np.delete(self.job_msgs, k),
                        np.delete(self.job_pairs, k),
                        np.delete(self.job_procs, k))
        new._set_time_views()
        return new

    def core_table(self, placement: Placement) -> np.ndarray:
        """Per-(job, rank) global core id, aligned with pair_src/pair_dst."""
        table = np.empty(self.n_procs, dtype=np.int64)
        for job in self.jobs:
            off = self.offsets[job.job_id]
            table[off:off + job.n_procs] = placement.assignments[job.job_id]
        return table


_FLAT_CACHE: OrderedDict[tuple, _WorkloadFlat] = OrderedDict()
_FLAT_CACHE_SIZE = 8


def set_flat_cache_size(n: int) -> None:
    """Resize the shared flattening cache (entries, LRU).

    The default of 8 covers one scheduler's churn; a cell-sharded fleet
    (DESIGN.md §13) keeps one warm ``_WorkloadFlat`` per cell alive
    concurrently, so ``FleetScheduler`` widens the cache to
    ``2 * n_cells + 4`` at construction. Shrinking evicts LRU entries.
    """
    global _FLAT_CACHE_SIZE
    _FLAT_CACHE_SIZE = max(1, int(n))
    while len(_FLAT_CACHE) > _FLAT_CACHE_SIZE:
        _FLAT_CACHE.popitem(last=False)


def _delta_steps(prev: _WorkloadFlat, jobs: Sequence[AppGraph]):
    """(removed job_ids, appended jobs) turning ``prev`` into ``jobs``.

    The scheduler's churn pattern only: survivors keep their relative
    order and new jobs are appended at the tail. Returns ``None`` when
    ``jobs`` is not reachable that way (or the rebuild would be as
    expensive as starting fresh).
    """
    cur_ids = [id(j) for j in jobs]
    cur_set = set(cur_ids)
    prev_set = {id(j) for j in prev.jobs}
    survivors = [id(j) for j in prev.jobs if id(j) in cur_set]
    added = [j for j in jobs if id(j) not in prev_set]
    if survivors + [id(j) for j in added] != cur_ids:
        return None
    removed = [j.job_id for j in prev.jobs if id(j) not in cur_set]
    if len(removed) + len(added) > max(2, len(jobs) // 2):
        return None
    return removed, added


def flatten_delta(jobs: Sequence[AppGraph], count_scale: float,
                  prev: _WorkloadFlat | None = None) -> _WorkloadFlat:
    """Warm-start flatten: patch ``prev`` instead of rebuilding when the
    job set changed by a few departures and/or appended arrivals — the
    online scheduler's per-event churn (DESIGN.md §3).
    """
    jobs = list(jobs)
    if prev is None or count_scale != prev.count_scale:
        return _flatten(jobs, count_scale)
    with obs.span("sim.flatten"):
        if [id(j) for j in jobs] == [id(j) for j in prev.jobs]:
            _count("sim.flatten.reuse")
            return prev
        steps = _delta_steps(prev, jobs)
        if steps is None:
            return _flatten_cached(jobs, count_scale)
        removed, added = steps
        flat = prev
        for jid in removed:
            flat = flat.with_job_removed(jid)
        for job in added:
            flat = flat.with_job_added(job)
        _cache_put(flat)
        _count("sim.flatten.delta")
        return flat


def _cache_put(flat: _WorkloadFlat) -> None:
    key = (tuple(id(j) for j in flat.jobs), flat.count_scale)
    _FLAT_CACHE[key] = flat
    _FLAT_CACHE.move_to_end(key)
    while len(_FLAT_CACHE) > _FLAT_CACHE_SIZE:
        _FLAT_CACHE.popitem(last=False)


def _flatten(jobs: Sequence[AppGraph], count_scale: float) -> _WorkloadFlat:
    with obs.span("sim.flatten"):
        return _flatten_cached(jobs, count_scale)


def _flatten_cached(jobs: Sequence[AppGraph],
                    count_scale: float) -> _WorkloadFlat:
    key = (tuple(id(j) for j in jobs), count_scale)
    flat = _FLAT_CACHE.get(key)
    if flat is None:
        flat = _WorkloadFlat(jobs, count_scale)
        _cache_put(flat)
        _count("sim.flatten.build")
    else:
        _FLAT_CACHE.move_to_end(key)
        _count("sim.flatten.cache_hit")
    return flat


# ---------------------------------------------------------------------------
# Routing
# ---------------------------------------------------------------------------
class _Stage:
    """One post-stage-0 multi-server Lindley pass, at PAIR granularity.

    Merged hierarchy hops with disjoint pair masks (and disjoint server
    id blocks), flattened into dense per-pair arrays.
    """

    __slots__ = ("mask", "sid", "service", "latency")

    def __init__(self, hops):
        self.mask = hops[0].mask.copy()
        self.sid = np.where(hops[0].mask, hops[0].server, 0)
        self.service = np.where(hops[0].mask, hops[0].service, 0.0)
        self.latency = np.where(hops[0].mask, hops[0].latency, 0.0)
        for h in hops[1:]:
            self.mask |= h.mask
            self.sid[h.mask] = h.server[h.mask]
            self.service[h.mask] = h.service[h.mask]
            self.latency[h.mask] = h.latency[h.mask]


def _route(cluster: ClusterTopology, s_core: np.ndarray, r_core: np.ndarray,
           size: np.ndarray):
    """Stage-0 server/service per message + later hierarchy stages.

    Server id spaces are disjoint so one scan covers any mix of levels:
    ``[0, N*S)`` cache sockets, then memory nodes, then one
    (level, direction) block per hierarchy hop (DESIGN.md §9 — the scan's
    per-level server axis). Stage 0 holds every message's FIRST server
    (cache / memory / first hierarchy hop with arrival == emit); each
    later stage is fed by the previous stage's departures.

    Returns ``(sid0, service0, stages)`` where ``stages`` is the list of
    post-stage-0 :class:`_Stage` passes in topological order.
    """
    node_map, sock_map, _ = cluster.core_maps()
    s_node = node_map[s_core]
    r_node = node_map[r_core]
    s_sock = sock_map[s_core]
    r_sock = sock_map[r_core]

    same_node = s_node == r_node
    same_sock = same_node & (s_sock == r_sock)
    via_cache = same_sock & (size <= cluster.cache_msg_cap)
    via_mem = same_node & ~via_cache
    inter = ~same_node

    n_sock = cluster.n_nodes * cluster.sockets_per_node
    sid1 = np.empty(size.size, dtype=np.int64)
    service = np.zeros(size.size, dtype=np.float64)

    if via_cache.any():
        sid1[via_cache] = s_node[via_cache] * cluster.sockets_per_node \
            + s_sock[via_cache]
        service[via_cache] = size[via_cache] / cluster.cache_bw
    if via_mem.any():
        penalty = np.where(s_sock[via_mem] != r_sock[via_mem],
                           1.0 + cluster.numa_remote_penalty, 1.0)
        sid1[via_mem] = n_sock + s_node[via_mem]
        service[via_mem] = size[via_mem] / cluster.mem_bw * penalty

    hier = cluster.net_hierarchy()
    hops = hier.pair_hops(s_core, r_core, size, n_cores=cluster.n_cores,
                          active=inter, server_base=n_sock + cluster.n_nodes)
    merged = hier.merge_stages(hops)
    first = merged[0] if merged else []
    placed = via_cache | via_mem
    for hop in first:
        sid1[hop.mask] = hop.server[hop.mask]
        service[hop.mask] = hop.service[hop.mask]
        placed |= hop.mask
    # messages whose first hop comes later (deep express configs), or that
    # cross no modelled level: park them on one zero-service bypass server
    # in stage 0 — waits stay exactly 0 there, the fast sorted path is
    # preserved, and their deliver time seeds the later stage correctly.
    if not placed.all():
        sid1[~placed] = int(sid1[placed].max(initial=0)) + 1 if placed.any() \
            else 0
    return sid1, service, [_Stage(h) for h in merged[1:]]


def _route_pairs(cluster: ClusterTopology, flat: _WorkloadFlat,
                 placement: Placement):
    """Route at pair granularity — all fields stay pair-level.

    Callers expand through ``flat.pair_of`` (or its sorted views) with
    one narrow gather wherever message granularity is actually needed.
    """
    cores = flat.core_table(placement)
    return _route(cluster, cores[flat.pair_src], cores[flat.pair_dst],
                  flat.pair_size)


def _round1_order(flat: _WorkloadFlat, sid1_p: np.ndarray):
    """Stable (server, arrival) order for round 1, built from cached
    pre-permuted views: one radix pass over narrow per-pair server ids.

    Returns (order, po_s, starts): original-index order, pair index per
    sorted message, segment-head mask.
    """
    if sid1_p.max() < np.iinfo(np.int16).max:
        sid1_p = sid1_p.astype(np.int16)    # radix sort + 2-byte gathers
    key_t = sid1_p[flat.pair_of_t]
    r = np.argsort(key_t, kind="stable").astype(np.int32)
    order = flat.time_order[r]
    po_s = flat.pair_of_t[r]
    starts = _segment_starts(key_t[r])
    return order, po_s, starts, r


# ---------------------------------------------------------------------------
# Stable (server, arrival) ordering
# ---------------------------------------------------------------------------
def _stable_sid_sort(sid: np.ndarray, time_order: np.ndarray) -> np.ndarray:
    """Stable-by-arrival order refined by server id (== np.lexsort, faster).

    Server ids are tiny, so the refining sort is an O(n) radix pass when
    they fit int16.
    """
    key = sid[time_order]
    if key.size and key.max() < np.iinfo(np.int16).max:
        key = key.astype(np.int16)
    return time_order[np.argsort(key, kind="stable")]


def _repair_ties(order: np.ndarray, sid_s: np.ndarray, arr_s: np.ndarray,
                 rank: np.ndarray | None = None) -> bool:
    """Reorder exact (server, arrival) tie runs to loop-backend semantics.

    Unstable sorts may leave messages with EQUAL arrival at the SAME
    server in arbitrary relative order; the loop backend's stable lexsort
    keeps flattening order. Tied runs are re-sorted in place by ascending
    ``rank[order]`` (original index when ``rank`` is None). Returns True
    if anything changed (caller must re-derive sorted views).
    """
    tie = (sid_s[1:] == sid_s[:-1]) & (arr_s[1:] == arr_s[:-1])
    if not tie.any():
        return False
    in_run = np.empty(order.size, dtype=bool)
    in_run[0] = False
    in_run[1:] = tie
    run_id = np.cumsum(~in_run)
    member = in_run.copy()
    member[:-1] |= tie                            # heads of tie runs too
    at = np.flatnonzero(member)
    key = order[at] if rank is None else rank[order[at]]
    fix = np.lexsort((key, run_id[at]))
    order[at] = order[at][fix]
    return True


def _order_by_server_arrival(sid: np.ndarray,
                             arrival: np.ndarray) -> np.ndarray:
    """(server, arrival)-sorted order with loop-backend tie semantics.

    An unstable float sort is ~5x faster than a stable one; stability only
    matters for the rare exactly-tied runs, repaired afterwards.
    """
    t_order = np.argsort(arrival)                 # unstable, fast
    order = _stable_sid_sort(sid, t_order)
    _repair_ties(order, sid[order], arrival[order])
    return order


# ---------------------------------------------------------------------------
# Segmented Lindley scans (inputs pre-sorted by (server, arrival))
# ---------------------------------------------------------------------------
def _segment_starts(sid_s: np.ndarray) -> np.ndarray:
    starts = np.empty(sid_s.size, dtype=bool)
    starts[0] = True
    np.not_equal(sid_s[1:], sid_s[:-1], out=starts[1:])
    return starts


def _increments(arr_s, srv_s, s_idx, out=None):
    """X_n per sorted message; 0 at segment heads (fresh server)."""
    x = np.empty(arr_s.size) if out is None else out
    x[0] = 0.0
    np.subtract(arr_s[1:], arr_s[:-1], out=x[1:])       # dA_n
    np.subtract(srv_s[:-1], x[1:], out=x[1:])           # S_{n-1} - dA_n
    x[s_idx] = 0.0
    return x


def _segmented_waits_numpy(arr_s, srv_s, starts):
    """W = M - running-min(M) per segment, M the segmented prefix sum of X.

    The per-segment offset of the GLOBAL prefix sum ``cs`` cancels in
    ``M - min M``, so W = cs - segmin(cs) directly.

    Fast path for segmin: scatter each segment onto its own row of a
    (servers, longest-queue) grid and run one dense
    ``np.minimum.accumulate``. When a skewed segment-length distribution
    would blow the grid up, fall back to doubling sweeps with an
    in-segment guard (after the sweep with step d, position i holds the
    min over ``[max(head_i, i - 2d + 1), i]`` — min never rounds, so both
    paths are exact).
    """
    n = arr_s.size
    s_idx = np.flatnonzero(starts)
    lens = np.diff(np.append(s_idx, n))
    cs = np.cumsum(_increments(arr_s, srv_s, s_idx))
    n_seg = s_idx.size
    width = int(lens.max())
    if n_seg * width <= max(4 * n, _DENSE_CUMMIN_CAP):
        # lin[i] = row_i * width + (i - head_i), built per segment
        rowbase = np.arange(n_seg, dtype=np.int64) * width - s_idx
        lin = (np.repeat(rowbase, lens)
               + np.arange(n, dtype=np.int64)).astype(np.int32)
        dense = np.full(n_seg * width, np.inf)
        dense[lin] = cs
        grid = dense.reshape(n_seg, width)
        np.minimum.accumulate(grid, axis=1, out=grid)
        return np.subtract(cs, dense[lin], out=cs)
    head = np.repeat(s_idx, lens)
    pos = np.arange(n) - head
    m = cs.copy()
    d = 1
    while d < width:
        cand = np.minimum(m[d:], m[:-d])
        m[d:] = np.where(pos[d:] >= d, cand, m[d:])
        d <<= 1
    return np.subtract(cs, m, out=cs)


def _u_elements(arr_s, srv_s, starts, out=None):
    """Max-plus scan elements' ``u``: interior (X_n, 0), segment head
    (-inf, 0). Every element's ``v`` is 0, so only ``u`` is built."""
    s_idx = np.flatnonzero(starts)
    u = _increments(arr_s, srv_s, s_idx, out)
    u[s_idx] = -np.inf
    return u


# Device rows are padded to a power of two, and to at least one block of
# the blocked jax scan — which is also a whole number of the Pallas
# kernel's (8, 128) tiles — so a churning live fleet hits a bounded set
# of compiled shapes.
_SCAN_BLOCK = 1024
# A padded jax row crosses the link in at most this many chunks, each at
# least one block: what lies past the real length wastes at most one chunk.
_LINK_CHUNKS = 8
_JAX_SCAN = None
_ZERO_CHUNKS: dict = {}       # (rows, width) -> device-resident zeros


def _combine(a, b):
    """Compose max-plus maps: ``a`` (earlier) followed by ``b``."""
    import jax.numpy as jnp
    au, av = a
    bu, bv = b
    return au + bu, jnp.maximum(av + bu, bv)


def _blocked_scan(u, v):
    """Inclusive max-plus scan along the last axis of power-of-two rows.

    One ``associative_scan`` over all n elements compiles super-linearly
    slowly for TPU (f64, TPU v5e target: 2^18 in 8 s, 2^20 in 86 s). Here
    each ``_SCAN_BLOCK``-wide block scans alone, the block totals scan
    recursively, and each block's exclusive prefix composes in front —
    the compile stays a few seconds at 2^23.
    """
    import jax
    import jax.numpy as jnp
    n = u.shape[-1]
    if n <= _SCAN_BLOCK:
        return jax.lax.associative_scan(_combine, (u, v), axis=-1)
    lead = u.shape[:-1]
    blocks = (*lead, n // _SCAN_BLOCK, _SCAN_BLOCK)
    lu, lv = jax.lax.associative_scan(
        _combine, (u.reshape(blocks), v.reshape(blocks)), axis=-1)
    tu, tv = _blocked_scan(lu[..., -1], lv[..., -1])
    # maps before each block: the inclusive totals shifted one block on
    pu = jnp.concatenate([jnp.zeros_like(tu[..., :1]), tu[..., :-1]], -1)
    pv = jnp.concatenate([jnp.full_like(tv[..., :1], -jnp.inf),
                          tv[..., :-1]], -1)
    big_u, big_v = _combine((pu[..., None], pv[..., None]), (lu, lv))
    return big_u.reshape(u.shape), big_v.reshape(v.shape)


def _jax_scan_fn():
    """The jitted device scan: the chunks of padded ``u`` rows in, the
    chunks of their waits out — one program per ``(rows, npad)``. ``v`` is
    0 on every element (``_u_elements``) and is made here, not sent;
    past a row's real length nothing is read back, so the elements there
    do not matter. Each real wait depends only on the elements before it,
    grouped by the padded shape alone."""
    global _JAX_SCAN
    if _JAX_SCAN is None:
        import jax
        import jax.numpy as jnp

        @jax.jit
        def scan(*chunks):
            u = jnp.concatenate(chunks, axis=-1)
            w = jnp.maximum(*_blocked_scan(u, jnp.zeros_like(u)))
            return jnp.split(w, len(chunks), axis=-1)

        _JAX_SCAN = scan
    return _JAX_SCAN


def _padded(n: int) -> int:
    """Device row width for ``n`` elements: a power of two, at least one
    scan block."""
    return max(_SCAN_BLOCK, 1 << int(n - 1).bit_length())


def _chunk_width(npad: int) -> int:
    """Width of the chunks a padded jax row crosses the link in, a
    function of ``npad`` alone: ``(rows, npad)`` keeps one program."""
    return npad // min(_LINK_CHUNKS, npad // _SCAN_BLOCK)


def _link_rows(rows: int, n: int) -> np.ndarray:
    """Host buffer for ``rows`` jax element rows, the longest ``n`` long:
    chunk-major ``(sent, rows, width)``, only the chunks that hold real
    elements, each contiguous (one chunk is one copy). The last partial
    chunk is zero past ``n``."""
    width = _chunk_width(_padded(n))
    sent = -(-n // width)
    buf = np.empty((sent, rows, width))
    buf[-1, :, n - (sent - 1) * width:] = 0.0
    return buf


def _put_row(buf: np.ndarray, k: int, u: np.ndarray) -> None:
    """Row ``k`` of a ``_link_rows`` buffer set to ``u``, zero past it."""
    width = buf.shape[-1]
    full, rem = divmod(u.size, width)
    buf[:full, k] = u[:full * width].reshape(full, width)
    if full < len(buf):
        buf[full, k, :rem] = u[full * width:]
        buf[full, k, rem:] = 0.0
        buf[full + 1:, k] = 0.0


def _waits_jax(u: np.ndarray, n: int) -> np.ndarray:
    """Waits of a ``_link_rows`` buffer's rows on the JAX backend, the
    first ``n`` of each, in f64. Its chunks cross the link; a cached
    device-resident zero chunk fills the slots of the padding chunks, and
    only the output chunks that cover ``n`` come back."""
    import jax
    sent, rows, width = u.shape
    slots = _padded(n) // width
    with jax.enable_x64(True):
        zero = _ZERO_CHUNKS.get((rows, width))
        if zero is None:
            zero = _ZERO_CHUNKS[rows, width] = jax.device_put(
                np.zeros((rows, width)))
        chunks = jax.device_put(list(u)) + [zero] * (slots - sent)
        out = _jax_scan_fn()(*chunks)[:sent]
        for c in out:
            c.copy_to_host_async()
        w = np.empty((rows, n))
        for j, c in enumerate(out):
            w[:, j * width:(j + 1) * width] = np.asarray(c)[:, :n - j * width]
    _count("sim.device.chunks_sent", sent)
    _count("sim.device.chunks_total", slots)
    _count("sim.device.d2h_bytes", u.nbytes)
    return w


def pallas_interpret() -> bool:
    """Whether the Pallas kernel runs interpreted: everywhere but a TPU."""
    import jax
    return jax.default_backend() != "tpu"


def _pad(u: np.ndarray, v: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """(batch, n) element rows padded with identity elements ``(0, -inf)``
    past the tail to a power of two, at least one scan block (the Pallas
    kernel's rows)."""
    n = u.shape[-1]
    widths = [(0, 0)] * (u.ndim - 1) + [(0, _padded(n) - n)]
    return (np.pad(u, widths, constant_values=0.0),
            np.pad(v, widths, constant_values=-np.inf))


def _stack_rows(rows) -> tuple[np.ndarray, np.ndarray]:
    """Ragged ``(u, v)`` rows — e.g. one per candidate placement's stage —
    stacked on the batch axis, identity elements past each row's tail."""
    u2 = np.zeros((len(rows), max(u.size for u, _ in rows)))
    v2 = np.full_like(u2, -np.inf)
    for i, (u, v) in enumerate(rows):
        u2[i, :u.size] = u
        v2[i, :v.size] = v
    return u2, v2


def _pack(rows, backend: str):
    """Ragged ``u`` rows as the backend's device scan takes them: a
    ``_link_rows`` buffer on ``jax``; on ``pallas`` the ``(u, v)`` rows
    padded with the identity past each row's tail."""
    if backend == "jax":
        buf = _link_rows(len(rows), max(u.size for u in rows))
        for k, u in enumerate(rows):
            _put_row(buf, k, u)
        return buf
    return _pad(*_stack_rows([(u, np.zeros(u.size)) for u in rows]))


def _device_scan(packed, n: int, backend: str) -> np.ndarray:
    """Waits of ``_pack``ed rows on the ``jax`` or ``pallas`` backend, the
    first ``n`` of each, back on the host in float64. The ``sim.device``
    span counts the bytes sent: the chunks of ``u`` on ``jax``, the
    padded float32 ``(u, v)`` rows the ``pallas`` kernel takes."""
    with obs.span("sim.device") as s:
        if backend == "jax":
            s.count(packed.nbytes)
            return _waits_jax(packed, n)
        from ..kernels.lindley_scan import lindley_scan
        u, v = packed
        s.count((u.size + v.size) * 4)
        w = lindley_scan(u, v, interpret=pallas_interpret())
        return np.asarray(w[..., :n], dtype=np.float64)


def _device_waits(u: np.ndarray, v: np.ndarray, backend: str) -> np.ndarray:
    """Waits of (batch, n) element rows on the ``jax`` or ``pallas``
    backend. ``pallas`` takes ``(u, v)``, padded with identity elements
    ``(0, -inf)`` past the tail. ``jax`` ignores ``v``: the simulator's
    elements all have ``v = 0`` (``_u_elements``), which its scan makes on
    the device, and nothing past the tail is read back. Called at
    ``(batch, power of two)`` it compiles exactly the programs the
    simulator's calls of that padded shape run, whatever ``v`` holds."""
    n = u.shape[-1]
    if backend == "jax":
        return _device_scan(_pack(list(u), backend), n, backend)
    return _device_scan(_pad(u, v), n, backend)


def _util_max(arr_s, srv_s, w_s, starts) -> float:
    """max over servers of busy/span — same definition as the loop backend."""
    s_idx = np.flatnonzero(starts)
    ends = np.append(s_idx[1:], arr_s.size)
    busy = np.add.reduceat(srv_s, s_idx)
    span = arr_s[ends - 1] + w_s[ends - 1] + srv_s[ends - 1] - arr_s[s_idx]
    return float((busy / np.maximum(span, _SPAN_FLOOR)).max())


def _pass_waits(arr_s, srv_s, starts, backend: str) -> np.ndarray:
    """Sorted-domain waits for one multi-server round on any backend."""
    if backend == "segmented":
        with obs.span("sim.scan"):
            return _segmented_waits_numpy(arr_s, srv_s, starts)
    n = arr_s.size
    with obs.span("sim.pack"):
        if backend == "jax":          # straight into the link's chunks
            packed = _link_rows(1, n)
            _u_elements(arr_s, srv_s, starts, out=packed.reshape(-1)[:n])
        else:
            packed = _pack([_u_elements(arr_s, srv_s, starts)], backend)
    return _device_scan(packed, n, backend)[0]


# ---------------------------------------------------------------------------
# Whole-workload simulation
# ---------------------------------------------------------------------------
def _empty_result(jobs) -> SimResult:
    """Message-free workload: still key every job (zero-traffic jobs must
    not vanish from per-job metrics — the scheduler indexes them)."""
    zeros = {job.job_id: 0.0 for job in jobs}
    return SimResult(0.0, dict(zeros), 0.0, dict(zeros), 0.0, 0, 0.0)


def _metrics(jobs, flat: _WorkloadFlat, wait, deliver, util) -> SimResult:
    nj = len(jobs)
    # job_row is non-decreasing (jobs flattened in order), so per-job sums
    # and maxes are reduceats over cached contiguous blocks
    nonempty = flat.job_nonempty
    block = flat.job_starts[nonempty]
    per = np.zeros(nj)
    per[nonempty] = np.add.reduceat(wait, block)
    finish = np.zeros(nj)
    finish[nonempty] = np.maximum.reduceat(deliver, block)
    per_job_wait = {job.job_id: float(per[k]) for k, job in enumerate(jobs)}
    job_finish = {job.job_id: float(finish[k]) for k, job in enumerate(jobs)}
    return SimResult(
        total_wait=float(wait.sum()),
        per_job_wait=per_job_wait,
        workload_finish=float(deliver.max()),
        job_finish=job_finish,
        total_job_finish=float(sum(job_finish.values())),
        n_messages=int(wait.size),
        max_server_utilisation=float(util),
    )


def simulate_scan(jobs: Sequence[AppGraph], placement: Placement,
                  cluster: ClusterTopology | None = None,
                  count_scale: float = 1.0,
                  backend: str = "segmented",
                  flat: _WorkloadFlat | None = None) -> SimResult:
    """Scan-backend equivalent of ``simulator.simulate`` (same metrics).

    ``flat`` lets a warm-start handle (``simulator.SimHandle``) pass a
    delta-assembled workload instead of going through the global cache.
    """
    cluster = cluster or placement.cluster
    placement.validate()
    if flat is None:
        flat = _flatten(jobs, count_scale)
    if flat.n_messages == 0:
        return _empty_result(jobs)
    with obs.span("sim.route"):
        sid1_p, service_p, stages = _route_pairs(cluster, flat, placement)

    # ---- stage 0: every message at its first server ----------------------
    with obs.span("sim.order"):
        order, po_s, starts, r = _round1_order(flat, sid1_p)
        arr_s = flat.emit_t[r]
        srv_s = service_p[po_s]
    w_s = _pass_waits(arr_s, srv_s, starts, backend)
    with obs.span("sim.reduce"):
        util = _util_max(arr_s, srv_s, w_s, starts)
        deliver_s = arr_s + w_s + srv_s
        n = flat.n_messages
        wait = np.empty(n)
        wait[order] = w_s
        deliver = np.empty(n)
        deliver[order] = deliver_s

    # ---- later stages: hierarchy hops fed by previous departures ---------
    for stage in stages:
        with obs.span("sim.order"):
            rows = np.flatnonzero(stage.mask[flat.pair_of])
            po = flat.pair_of[rows]
            arrive = deliver[rows] + stage.latency[po]
            srv2 = stage.service[po]
            sid2 = stage.sid[po]
            # FIFO departures are monotone per previous server, so
            # ``arrive`` is a concatenation of ascending runs — timsort
            # merges cheaply
            t2 = np.argsort(arrive, kind="stable")
            o2 = _stable_sid_sort(sid2, t2)
            sid2_s = sid2[o2]
            arr2_s = arrive[o2]
            # the stable sort above keeps prior-stage order on ties; the
            # loop backend keeps ORIGINAL order — repair the (rare) tied runs
            if _repair_ties(o2, sid2_s, arr2_s, rank=rows):
                sid2_s = sid2[o2]
                arr2_s = arrive[o2]
            starts2 = _segment_starts(sid2_s)
            srv2_s = srv2[o2]
        w2_s = _pass_waits(arr2_s, srv2_s, starts2, backend)
        with obs.span("sim.reduce"):
            util = max(util, _util_max(arr2_s, srv2_s, w2_s, starts2))
            rows2 = rows[o2]
            wait[rows2] += w2_s
            deliver[rows2] = arr2_s + w2_s + srv2_s
    with obs.span("sim.reduce"):
        return _metrics(jobs, flat, wait, deliver, util)


# ---------------------------------------------------------------------------
# Batched candidate evaluation (device backends)
# ---------------------------------------------------------------------------
def simulate_scan_batch(jobs: Sequence[AppGraph],
                        placements: Sequence[Placement],
                        cluster: ClusterTopology | None = None,
                        count_scale: float = 1.0,
                        backend: str = "jax",
                        flat: _WorkloadFlat | None = None) -> list[SimResult]:
    """Score K placements of one job set with one batched scan per stage.

    Placements share jobs and message count M, so stage-0 rows stack into
    a dense (K, M) batch; later-stage row lengths differ per placement
    (routing differs, and deeper hierarchies differ in stage count) and
    are padded with identity elements past the real tail — the kernel's
    level/batch row axis (DESIGN.md §9).
    """
    if not placements:
        return []
    cluster = cluster or placements[0].cluster
    if flat is None:
        flat = _flatten(jobs, count_scale)
    if flat.n_messages == 0:
        return [_empty_result(jobs) for _ in placements]
    for p in placements:
        p.validate()

    # One loop over the K placements per phase, not one per placement
    # through every phase: each phase is one host span whatever K is.
    K = len(placements)
    with obs.span("sim.route"):
        routed = [_route_pairs(cluster, flat, p) for p in placements]
    with obs.span("sim.order"):
        ordered = [_round1_order(flat, sid1_p) for sid1_p, _, _ in routed]
    with obs.span("sim.pack"):
        u1 = _pack([_u_elements(flat.emit_t[r], service_p[po_s], starts)
                    for (_, service_p, _), (_, po_s, starts, r)
                    in zip(routed, ordered)], backend)
    w1 = _device_scan(u1, flat.n_messages, backend)
    results_state = []
    with obs.span("sim.reduce"):
        for k, ((_, service_p, _), (order, _, starts, _)) in enumerate(
                zip(routed, ordered)):
            service = service_p[flat.pair_of]
            arr_s, srv_s = flat.emit[order], service[order]
            w_s = w1[k]
            util = _util_max(arr_s, srv_s, w_s, starts)
            wait = np.empty_like(w_s)
            wait[order] = w_s
            deliver = flat.emit + wait + service
            results_state.append({"wait": wait, "deliver": deliver,
                                  "util": util})

    n_stages = max(len(stages) for _, _, stages in routed)
    for si in range(n_stages):
        passes: list[dict | None] = [None] * K
        with obs.span("sim.order"):
            for k, (_, _, stages) in enumerate(routed):
                if si >= len(stages):
                    continue
                stage = stages[si]
                idx2 = np.flatnonzero(stage.mask[flat.pair_of])
                if idx2.size == 0:
                    continue
                po = flat.pair_of[idx2]
                arrive = results_state[k]["deliver"][idx2] + stage.latency[po]
                srv = stage.service[po]
                sid2 = stage.sid[po]
                order = _order_by_server_arrival(sid2, arrive)
                starts = _segment_starts(sid2[order])
                passes[k] = {"idx2": idx2, "arrive": arrive, "srv": srv,
                             "order": order, "starts": starts}
        live = [p2 for p2 in passes if p2 is not None]
        if not live:
            continue
        # stage rows are ragged (routing differs per placement)
        with obs.span("sim.pack"):
            ragged = []
            for p2 in live:
                order = p2["order"]
                p2["row"] = len(ragged)
                ragged.append(_u_elements(p2["arrive"][order],
                                          p2["srv"][order], p2["starts"]))
            width = max(u.size for u in ragged)
            u2 = _pack(ragged, backend)
        w2 = _device_scan(u2, width, backend)
        with obs.span("sim.reduce"):
            for k, p2 in enumerate(passes):
                if p2 is None:
                    continue
                rs = results_state[k]
                idx2, order, starts = p2["idx2"], p2["order"], p2["starts"]
                arr_s, srv_s = p2["arrive"][order], p2["srv"][order]
                w_s = w2[p2["row"], :idx2.size]
                rs["util"] = max(rs["util"],
                                 _util_max(arr_s, srv_s, w_s, starts))
                w_rx = np.empty_like(w_s)
                w_rx[order] = w_s
                rs["wait"][idx2] += w_rx
                rs["deliver"][idx2] = p2["arrive"] + w_rx + p2["srv"]

    with obs.span("sim.reduce"):
        return [_metrics(jobs, flat, rs["wait"], rs["deliver"],
                         rs["util"]) for rs in results_state]
