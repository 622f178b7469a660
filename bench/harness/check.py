"""Work counts and the comparison that decides ``correct``.

After the window closes, a sample of the placements the window scored —
drawn from the seed, always with the largest call in it — is re-run
through the plain reference (``reference.simulate``) on the same job
set, placement and message-count scale, and every answer the program
returned for it is compared (:func:`rel_errs`), as three numbers:
``sim_max_rel_err`` (per-job and workload finish times, the largest
server utilisation, the message count), ``wait_rel_err`` (the total
wait) and ``job_wait_err`` (each job's wait, against the total). Beside that, the generator's own validity counts (cores booked
twice, jobs with the wrong number of cores, scheduler invariants, remap
commits that gain nothing) must be zero. Each number has its limit in
the traffic file.
"""
from __future__ import annotations

import time

import numpy as np

from .reference import Topology, simulate


def _rel(a: float, b: float) -> float:
    return abs(a - b) / abs(b) if b else abs(a)


def rel_errs(res, ref: dict) -> dict:
    """Errors of one SimResult's answers against the reference, by name.

    Finish times, the largest utilisation and the total wait are taken
    relative to their own reference value. A job's wait is taken
    relative to the total wait: where two messages of different jobs
    reach one server at arrival times equal to rounding, float64 may
    order them either way, and the later one's wait is then booked to
    the other job; that moves at most a few service times between jobs
    and leaves the total alone."""
    inf = float("inf")
    if int(res.n_messages) != ref["n_messages"]:
        return {"n_messages": inf}
    errs = {k: _rel(getattr(res, k), ref[k]) for k in
            ("total_wait", "workload_finish", "total_job_finish", "max_server_utilisation")}
    for key in ("per_job_wait", "job_finish"):
        if set(getattr(res, key)) != set(ref[key]):
            return {key: inf}
    errs.update({f"job_finish[{j}]": _rel(res.job_finish[j], v)
                 for j, v in ref["job_finish"].items()})
    total = abs(ref["total_wait"]) or 1.0
    errs.update({f"per_job_wait[{j}]": abs(res.per_job_wait[j] - v) / total
                 for j, v in ref["per_job_wait"].items()})
    return errs


NUMBERS = {   # number compared -> the answers it covers
    "sim_max_rel_err": ("workload_finish", "total_job_finish", "max_server_utilisation",
                        "job_finish", "n_messages"),
    "wait_rel_err": ("total_wait",),
    "job_wait_err": ("per_job_wait",),
}


def split(errs: dict) -> dict:
    """Largest error of each number compared, with the answer it is in."""
    out = {}
    for number, keys in NUMBERS.items():
        mine = {k: v for k, v in errs.items() if k.split("[")[0] in keys}
        if "n_messages" in errs or "per_job_wait" in errs or "job_finish" in errs:
            mine = {k: float("inf") for k in errs}
        name = max(mine, key=mine.get) if mine else ""
        out[number] = (mine.get(name, 0.0), name)
    return out


def count_hops(topo: Topology, calls: list, row_of: dict) -> list[int]:
    """Message-hops of every placement each call scored (one sum per call).

    Hops are additive over jobs, and a job's hops depend only on its own
    cores, so they are cached per (job, cores array, scale)."""
    from .reference import message_hops
    cache: dict = {}
    out = []
    for call in calls:
        total = 0
        for pl in call.placements:
            for jid in call.job_ids:
                cores = pl[jid]
                key = (jid, id(cores), call.count_scale)
                if key not in cache:
                    cache[key] = (message_hops(topo, [row_of[jid]], [jid], {jid: cores},
                                               call.count_scale), cores)
                total += cache[key][0]
        out.append(total)
    return out


def work_counts(units: list, calls: list, hops: list[int]) -> dict:
    kinds: dict = {}
    kind_msgs: dict = {}
    at = 0
    for u in units:
        kinds[u.kind] = kinds.get(u.kind, 0) + 1
        mine = calls[at:at + u.calls]
        at += u.calls
        kind_msgs[u.kind] = kind_msgs.get(u.kind, 0) + sum(
            c.rows * int(c.results[0].n_messages) for c in mine if c.results)
    rows = sum(c.rows for c in calls)
    msgs = sum(c.rows * int(c.results[0].n_messages) for c in calls if c.results)
    n, k = max(len(units), 1), max(len(calls), 1)
    return {
        "units": len(units),
        "mix": "/".join(f"{a}:{b}" for a, b in sorted(kinds.items())),
        "sim_calls": len(calls),
        "calls_per_unit": len(calls) / n,
        "rows_per_call": rows / k,
        "messages_per_call": msgs / k,
        "messages_per_unit": msgs / n,
        "hops_per_unit": sum(hops) / n,
        "hops_per_placement": sum(hops) / max(rows, 1),
        "messages_per_kind": "/".join(f"{a}:{kind_msgs[a] / b:.0f}"
                                      for a, b in sorted(kinds.items())),
    }


def check(seed: int, traffic: dict, topo: Topology, calls: list, row_of: dict,
          validity: dict) -> dict:
    limits = traffic["limits"]
    rng = np.random.default_rng([int(seed), 7])
    rows = [(i, r) for i, c in enumerate(calls) for r in range(c.rows)]
    want = int(traffic["check_rows"])
    picked: list = []
    if rows:
        big = max(range(len(calls)),
                  key=lambda i: (calls[i].rows * int(calls[i].results[0].n_messages), -i))
        picked = [(big, r) for r in range(calls[big].rows)][:want]
        rest = [x for x in rows if x[0] != big]
        extra = max(0, want - len(picked))
        if rest and extra:
            idx = rng.choice(len(rest), size=min(extra, len(rest)), replace=False)
            picked += [rest[i] for i in sorted(idx)]
    t0 = time.perf_counter()
    largest = 0
    worst = {n: (0.0 if picked else float("inf"), "") for n in NUMBERS}
    for i, r in picked:
        call = calls[i]
        ref = simulate(topo, [row_of[j] for j in call.job_ids], call.job_ids,
                       call.placements[r], call.count_scale)
        largest = max(largest, ref["n_messages"])
        for number, (err, name) in split(rel_errs(call.results[r], ref)).items():
            if err > worst[number][0]:
                worst[number] = (err, f"{name} of call {i} row {r}")
    where = "; ".join(f"{n} in {w[1]}" for n, w in worst.items() if w[1])
    print(f"check: reference over {len(picked)} of {len(rows)} placements scored "
          f"({largest} messages the largest) took {time.perf_counter() - t0:.3f} s; "
          f"largest: {where}; {validity.get('info', '')}", flush=True)
    values = {n: w[0] for n, w in worst.items()}
    values.update({k: v for k, v in validity.items() if k != "info"})
    numbers = {k: {"value": float(min(v, 1e308)) if isinstance(v, float) else int(v),
                   "limit": limits[k]} for k, v in values.items()}
    correct = all(n["value"] <= n["limit"] for n in numbers.values())
    return {"correct": bool(correct), "numbers": numbers}
