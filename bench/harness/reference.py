"""Plain reference simulator and the message-hop count.

This is the benchmark's own statement of what the scheduler's simulator
must compute, written without the program: every message of every job
is expanded, routed onto its FIFO servers, and each server's waits
follow Lindley's recursion in a per-server loop (arrival order, ties in
expansion order).

Semantics, as the configuration files state them:

* a job is a row of the configuration's mix (pattern, processes, bytes,
  rate, count); pattern pairs are taken row-major; a pair sends
  ``max(1, rint(count * count_scale))`` messages, message ``k`` at
  ``phase(job, sender) + k / rate``;
* a message between two cores of one socket no larger than
  ``cache_msg_cap`` queues at that socket's cache server; any other
  message inside a node queues at the node's memory server, 10% slower
  across sockets; a message between nodes queues at the TX server of
  every network level it crosses going up, pays the outermost crossed
  level's latency once, then queues at the RX server of every crossed
  level coming down; a level's TX and RX servers are one per group of
  that level.

The same routing gives :func:`message_hops`: one hop per (message,
server) visit on the route.
"""
from __future__ import annotations

import numpy as np

PATTERNS = ("all_to_all", "bcast_scatter", "gather_reduce", "linear")


def pattern_pairs(pattern: str, procs: int) -> tuple[np.ndarray, np.ndarray]:
    """(sender, receiver) ranks of one pattern, row-major."""
    if pattern == "all_to_all":
        src, dst = np.nonzero(~np.eye(procs, dtype=bool))
    elif pattern == "bcast_scatter":
        src, dst = np.zeros(procs - 1, np.int64), np.arange(1, procs)
    elif pattern == "gather_reduce":
        src, dst = np.arange(1, procs), np.zeros(procs - 1, np.int64)
    elif pattern == "linear":
        src, dst = np.arange(procs - 1), np.arange(1, procs)
    else:
        raise ValueError(f"unknown pattern {pattern!r}")
    return src.astype(np.int64), dst.astype(np.int64)


def phase(job_id: int, rank: np.ndarray) -> np.ndarray:
    """Per-(job, sender) emission offset that breaks simultaneous ticks."""
    r = np.asarray(rank, dtype=np.int64)
    return ((np.int64(job_id) * 2654435761 + r * 7919) % 104729) * 1e-9


def messages_per_pair(row: dict, count_scale: float) -> int:
    return max(1, int(np.rint(row["count"] * count_scale)))


class Topology:
    """Core -> node / socket / network groups, from the configuration."""

    def __init__(self, cluster: dict):
        self.c = cluster
        self.cps = int(cluster["cores_per_socket"])
        self.cpn = int(cluster["sockets_per_node"]) * self.cps
        self.n_cores = int(cluster["n_nodes"]) * self.cpn
        self.levels = cluster["levels"]
        size, self.group = 1, []
        for lv in self.levels:
            size *= int(lv["fan_in"])
            self.group.append(size)

    def path(self, s: np.ndarray, r: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """(in_path (L, P) bool, outermost crossed level (P,))."""
        cross = np.stack([s // g != r // g for g in self.group])
        return cross, cross.sum(axis=0) - 1

    def hops_per_message(self, s: np.ndarray, r: np.ndarray) -> np.ndarray:
        """Servers a message between cores ``s`` and ``r`` queues at."""
        inter = s // self.cpn != r // self.cpn
        in_path, _ = self.path(s, r)
        return np.where(inter, 2 * in_path.sum(axis=0), 1)


def message_hops(topo: Topology, rows: list[dict], job_ids: list[int],
                 placement: dict, count_scale: float) -> int:
    """Message-hops of one placement of a job set (see module docstring)."""
    total = 0
    for jid, row in zip(job_ids, rows):
        cores = np.asarray(placement[jid], dtype=np.int64)
        src, dst = pattern_pairs(row["pattern"], int(row["procs"]))
        hops = topo.hops_per_message(cores[src], cores[dst])
        total += int(hops.sum()) * messages_per_pair(row, count_scale)
    return total


def _fifo(server: np.ndarray, arrival: np.ndarray, service: np.ndarray):
    """Waits of a set of messages at their servers, and the largest
    busy/span share of any server. Per server: arrival order, ties in
    input order; W_n = max(0, W_{n-1} + S_{n-1} - (A_n - A_{n-1}))."""
    wait = np.zeros(arrival.size)
    util = 0.0
    if arrival.size == 0:
        return wait, util
    order = np.lexsort((arrival, server))
    srv_sorted = server[order]
    cut = np.flatnonzero(np.diff(srv_sorted)) + 1
    for seg in np.split(order, cut):
        a, s = arrival[seg], service[seg]
        x = s[:-1] - np.diff(a)
        m = np.concatenate([[0.0], np.cumsum(x)])
        w = m - np.minimum.accumulate(m)
        wait[seg] = w
        span = (a[-1] + w[-1] + s[-1]) - a[0]
        util = max(util, float(s.sum()) / max(span, 1e-30))
    return wait, util


def simulate(topo: Topology, rows: list[dict], job_ids: list[int],
             placement: dict, count_scale: float) -> dict:
    """Reference answers for one placement of a job set."""
    c = topo.c
    job, emit, s_core, r_core, size = [], [], [], [], []
    for k, (jid, row) in enumerate(zip(job_ids, rows)):
        cores = np.asarray(placement[jid], dtype=np.int64)
        src, dst = pattern_pairs(row["pattern"], int(row["procs"]))
        n = messages_per_pair(row, count_scale)
        period = 1.0 / float(row["rate"])
        ticks = np.arange(n) * period
        emit.append((phase(jid, src)[:, None] + ticks[None, :]).ravel())
        s_core.append(np.repeat(cores[src], n))
        r_core.append(np.repeat(cores[dst], n))
        size.append(np.full(src.size * n, float(row["bytes"])))
        job.append(np.full(src.size * n, k))
    emit = np.concatenate(emit)
    s_core, r_core = np.concatenate(s_core), np.concatenate(r_core)
    size, job = np.concatenate(size), np.concatenate(job)

    s_node, r_node = s_core // topo.cpn, r_core // topo.cpn
    s_sock = (s_core % topo.cpn) // topo.cps
    r_sock = (r_core % topo.cpn) // topo.cps
    same_node = s_node == r_node
    via_cache = same_node & (s_sock == r_sock) & (size <= c["cache_msg_cap"])
    via_mem = same_node & ~via_cache
    inter = ~same_node

    wait = np.zeros(emit.size)
    deliver = np.empty(emit.size)
    util = 0.0
    if via_cache.any():
        i = np.flatnonzero(via_cache)
        service = size[i] / c["cache_bw"]
        w, u = _fifo(s_node[i] * c["sockets_per_node"] + s_sock[i], emit[i], service)
        wait[i] += w
        deliver[i] = emit[i] + w + service
        util = max(util, u)
    if via_mem.any():
        i = np.flatnonzero(via_mem)
        penalty = np.where(s_sock[i] != r_sock[i], 1.0 + c["numa_remote_penalty"], 1.0)
        service = size[i] / c["mem_bw"] * penalty
        w, u = _fifo(s_node[i], emit[i], service)
        wait[i] += w
        deliver[i] = emit[i] + w + service
        util = max(util, u)
    if inter.any():
        i = np.flatnonzero(inter)
        s, r = s_core[i], r_core[i]
        in_path, lca = topo.path(s, r)
        cur = emit[i].copy()
        n_lv = len(topo.levels)
        route = [(k, "tx") for k in range(n_lv)] + [(k, "rx") for k in reversed(range(n_lv))]
        for k, direction in route:
            m = in_path[k]
            if not m.any():
                continue
            lv = topo.levels[k]
            core = s if direction == "tx" else r
            service = size[i][m] / lv["bw"]
            arrive = cur[m]
            if direction == "rx":
                arrive = arrive + np.where(lca[m] == k, lv["latency"], 0.0)
            w, u = _fifo(core[m] // topo.group[k], arrive, service)
            wait[i[m]] += w
            cur[m] = arrive + w + service
            util = max(util, u)
        deliver[i] = cur

    n_jobs = len(job_ids)
    per_wait = np.bincount(job, weights=wait, minlength=n_jobs)
    finish = np.full(n_jobs, -np.inf)
    np.maximum.at(finish, job, deliver)
    return {
        "n_messages": int(emit.size),
        "total_wait": float(wait.sum()),
        "workload_finish": float(deliver.max()),
        "total_job_finish": float(finish.sum()),
        "max_server_utilisation": util,
        "per_job_wait": {jid: float(per_wait[k]) for k, jid in enumerate(job_ids)},
        "job_finish": {jid: float(finish[k]) for k, jid in enumerate(job_ids)},
    }
