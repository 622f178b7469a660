"""What every traffic generator shares, and the loader that finds one.

A traffic file (``bench/traffic/<traffic>.json``) is data: it names a
generator and gives its parameters. The generator is a file of its own,
``bench/generators/<generator>.py``, which defines ``Traffic``, a
subclass of :class:`Generator`: it builds its state from the
configuration, the traffic file and the seed in ``prepare`` (set-up),
then runs one *unit* of work per ``unit`` call inside the measured
window. The seed changes which jobs, in what order, which moves and
where processes land; every generator is built so that it does not
change how much simulated work a unit does.

Settings of the program under test come from the data too: a traffic
file's ``scheduler`` object is turned into a ``SchedulerConfig`` by
:func:`scheduler_config`, group by group, so a new cell that needs other
scheduler settings brings them in its traffic file.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import os

import numpy as np

GENERATORS_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                              "generators")


def load(name: str):
    """The ``Traffic`` class of ``bench/generators/<name>.py``."""
    path = os.path.join(GENERATORS_DIR, name + ".py")
    if not os.path.isfile(path):
        raise KeyError(f"no generator {name!r} at {path}")
    spec = importlib.util.spec_from_file_location(f"bench_generator_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.Traffic


def build_cluster(cfg: dict):
    from repro.core.graphs import ClusterTopology
    from repro.core.hierarchy import NetLevel, NetworkHierarchy
    c = cfg["cluster"]
    levels = []
    for lv in c["levels"]:
        unknown = set(lv) - {"name", "fan_in", "bw", "latency"}
        if unknown:   # the reference routes plain levels only
            raise ValueError(f"network level {lv['name']!r}: unsupported keys {sorted(unknown)}")
        levels.append(NetLevel(lv["name"], fan_in=int(lv["fan_in"]), bw=float(lv["bw"]),
                               latency=float(lv["latency"])))
    return ClusterTopology(
        n_nodes=int(c["n_nodes"]), sockets_per_node=int(c["sockets_per_node"]),
        cores_per_socket=int(c["cores_per_socket"]), mem_bw=float(c["mem_bw"]),
        cache_bw=float(c["cache_bw"]), cache_msg_cap=float(c["cache_msg_cap"]),
        nic_bw=float(c["nic_bw"]), switch_latency=float(c["switch_latency"]),
        numa_remote_penalty=float(c["numa_remote_penalty"]),
        hierarchy=NetworkHierarchy(levels))


def scheduler_config(settings: dict, rng: np.random.Generator):
    """``SchedulerConfig`` from a traffic file's ``scheduler`` object.

    A key naming one of the config's groups (``remap``, ``admission``,
    ``recovery``, ``cells``, ``autoscale``) takes an object of that
    group's fields; any other key is one of the config's own fields.
    Every group with an ``rng_seed`` that the data leaves out gets one
    drawn from the run's seed."""
    from repro.sched import SchedulerConfig
    kw = {}
    for f in dataclasses.fields(SchedulerConfig):
        group = f.default_factory if f.default_factory is not dataclasses.MISSING else None
        if group is None or not dataclasses.is_dataclass(group):
            if f.name in settings:
                kw[f.name] = settings[f.name]
            continue
        fields = dict(settings.get(f.name, {}))
        if "rng_seed" in {g.name for g in dataclasses.fields(group)}:
            fields.setdefault("rng_seed", int(rng.integers(2**63)))
        kw[f.name] = group(**fields)
    unknown = set(settings) - set(kw)
    if unknown:
        raise KeyError(f"unknown scheduler settings {sorted(unknown)}")
    return SchedulerConfig(**kw)


def pairs_per_job(row: dict) -> int:
    p = int(row["procs"])
    return p * (p - 1) if row["pattern"] == "all_to_all" else p - 1


class Generator:
    def __init__(self, cfg: dict, traffic: dict, seed: int, probe):
        self.cfg = cfg
        self.t = traffic
        self.mix = cfg["mix"]
        self.rng = np.random.default_rng(seed)
        self.probe = probe
        self.cluster = build_cluster(cfg)
        self.n_cores = self.cluster.n_cores
        self.row_of: dict[int, dict] = {}     # job id -> mix row
        self.template_of: dict[int, int] = {}  # job id -> mix index
        self._next_id = 0

    # -- jobs --------------------------------------------------------------
    def new_job(self, i: int):
        from repro.core.graphs import AppGraph
        row = self.mix[i]
        jid = self._next_id
        self._next_id += 1
        self.row_of[jid] = row
        self.template_of[jid] = i
        return AppGraph.from_pattern(
            name=f"{row['pattern']}{row['procs']}@{jid}", pattern=row["pattern"],
            n_procs=int(row["procs"]), length=float(row["bytes"]),
            rate=float(row["rate"]), count=int(row["count"]), job_id=jid)

    def whole_cycles(self, shuffled: bool | None = None) -> list[int]:
        """As many whole cycles of the mix as fit, each in an order drawn
        from the seed, or in the configuration's own order where the
        traffic's ``fill`` is ``table`` (or ``shuffled`` is False); then
        the cores left are topped up with the largest templates that
        still fit, in the configuration's order. Every seed gets the same
        multiset of templates, and a cluster whose mix can fill it is
        full."""
        procs = [int(r["procs"]) for r in self.mix]
        cycles = max(1, self.n_cores // sum(procs))
        n = len(self.mix)
        if shuffled is None:
            shuffled = self.t.get("fill", "shuffled") == "shuffled"
        if shuffled:
            order = [int(i) for _ in range(cycles) for i in self.rng.permutation(n)]
        else:
            order = list(range(n)) * cycles
        free = self.n_cores - cycles * sum(procs)
        for i in sorted(range(n), key=lambda i: -procs[i]):
            while procs[i] <= free:
                order.append(i)
                free -= procs[i]
        return order

    def symmetry(self) -> np.ndarray:
        """A random automorphism of the cluster drawn from the seed: new
        core id of every core. Cores in a socket, sockets in a node, and
        the groups of every network level inside their parent group are
        each shuffled; every route keeps its servers' kinds and levels,
        so a placement and its image send the same messages the same
        number of hops."""
        c = self.cfg["cluster"]
        cps = int(c["cores_per_socket"])
        sizes = {1, cps, cps * int(c["sockets_per_node"]), self.n_cores}
        size = 1
        for lv in c["levels"]:
            size *= int(lv["fan_in"])
            sizes.add(size)
        sizes = sorted(sizes)
        assert all(b % a == 0 for a, b in zip(sizes, sizes[1:])), sizes

        def image(base: int, level: int) -> list[int]:
            if level == 0:
                return [base]
            child = sizes[level - 1]
            out: list[int] = []
            for k in self.rng.permutation(sizes[level] // child):
                out += image(base + int(k) * child, level - 1)
            return out

        return np.asarray(image(0, len(sizes) - 1), dtype=np.int64)

    def pinned_fill(self):
        """Fill order, and a strategy that places the fill as the seed's
        symmetric image of one fixed placement.

        The fixed placement is the configuration's strategy admitting
        whole cycles of the mix in the configuration's order, one job at
        a time; the seed draws the automorphism and the order in which
        the jobs are submitted (hence their ids). Every later placement
        is the configuration's strategy's own, made in the frame of the
        fixed placement (the free cores mapped back through the
        automorphism) and carried over by it, so the fleet stays the
        seed's image of one seed-free history of placements."""
        from repro.core.graphs import AppGraph, FreeCoreTracker, Placement
        from repro.core.mapping import STRATEGIES
        base = STRATEGIES[self.cfg["strategy"]]
        table = self.whole_cycles(shuffled=False)
        tracker = FreeCoreTracker(self.cluster)
        fixed = []
        for k, i in enumerate(table):
            row = self.mix[i]
            g = AppGraph.from_pattern(name="fill", pattern=row["pattern"], n_procs=int(row["procs"]),
                                      length=float(row["bytes"]), rate=float(row["rate"]),
                                      count=int(row["count"]), job_id=k)
            fixed.append(base([g], self.cluster, tracker).assignments[k])
        image = self.symmetry()
        order = [int(k) for k in self.rng.permutation(len(table))]
        self.pinned = {}                      # job id -> cores, used once
        jobs = []
        for k in order:
            job = self.new_job(table[k])
            self.pinned[job.job_id] = image[fixed[k]]
            jobs.append(job)

        def strategy(graphs, cluster, tracker=None):
            if len(graphs) == 1 and graphs[0].job_id in self.pinned:
                cores = self.pinned.pop(graphs[0].job_id)
                tracker.take_cores(cores)
                placement = Placement(cluster)
                placement.assign(graphs[0].job_id, cores)
                return placement
            frame = FreeCoreTracker(cluster, occupied=tracker.used[image])
            frame.offline |= tracker.offline[image]
            placement = Placement(cluster)
            for jid, cores in base(graphs, cluster, frame).assignments.items():
                tracker.take_cores(image[cores])
                placement.assign(jid, image[cores])
            return placement

        strategy.__name__ = self.cfg["strategy"]
        return jobs, strategy

    def stage0_messages(self, order: list[int], count_scale: float) -> int:
        n = 0
        for i in order:
            row = self.mix[i]
            n += pairs_per_job(row) * max(1, int(np.rint(row["count"] * count_scale)))
        return n

    def snapshot(self, order: list[int]):
        """Admit ``order`` one job at a time with the configured strategy,
        as the scheduler's admission path places arrivals."""
        from repro.core.graphs import FreeCoreTracker, Placement
        from repro.core.mapping import STRATEGIES
        jobs = [self.new_job(i) for i in order]
        tracker = FreeCoreTracker(self.cluster)
        placement = Placement(self.cluster)
        for job in jobs:
            pl = STRATEGIES[self.cfg["strategy"]]([job], self.cluster, tracker)
            placement.assign(job.job_id, pl.assignments[job.job_id])
        return jobs, placement, tracker

    # -- interface -----------------------------------------------------------
    warm_rows: tuple = (1,)

    def prepare(self) -> dict:
        raise NotImplementedError

    def warm_elements(self) -> int:
        raise NotImplementedError

    def unit(self) -> str:
        raise NotImplementedError

    def validity(self) -> dict:
        raise NotImplementedError

    def release(self) -> None:
        pass


def placement_faults(assignments: dict, row_of: dict, n_cores: int) -> int:
    """Cores booked twice, out of range, or a job with the wrong count."""
    faults = 0
    if not assignments:
        return 0
    cores = np.concatenate([np.asarray(c) for c in assignments.values()])
    if cores.min() < 0 or cores.max() >= n_cores:
        faults += 1
    faults += int(cores.size - np.unique(cores).size)
    for jid, c in assignments.items():
        if np.asarray(c).size != int(row_of[jid]["procs"]):
            faults += 1
    return faults

