"""Mapper-on-TPU benchmark: the paper's technique on the fleet
(EXPERIMENTS.md §Mapping-on-TPU).

Scenario A — single pod-spanning job (one per arch x train_4k on the
2x16x16 production mesh): static pod-crossing bytes and the max per-host
NIC load under blocked / cyclic / drb / paper-new / new_tpu.

Scenario B — multi-job fleet (the paper's actual setting): a mixed
training+serving job set sharing 2 pods; aggregate NIC metrics plus the
queueing-simulator waiting time with TPU constants (the paper's main
metric, re-based to the fleet).
"""
from __future__ import annotations

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs import SHAPES, get_config
from repro.core.meshplan import (JobSpec, compare_strategies, fleet_nic_load,
                                 place_jobs, tpu_topology)
from repro.core.simulator import simulate

STRATS = ("blocked", "cyclic", "drb", "new", "new_tpu")


def scenario_a(out=print):
    out("# scenario A: single 512-chip job, pod(2) x data(16) x model(16)")
    out("arch,strategy,dcn_GBps,max_nic_GBps,nic_oversub,ici_GBps")
    mesh_axes = {"pod": 2, "data": 16, "model": 16}
    topo = tpu_topology(n_pods=2)
    for arch in ("yi-6b", "phi3.5-moe-42b-a6.6b", "granite-3-2b",
                 "qwen2-moe-a2.7b"):
        cfg = get_config(arch)
        res = compare_strategies(cfg, SHAPES["train_4k"], mesh_axes, topo,
                                 strategies=STRATS)
        for s in STRATS:
            m = res[s].metrics
            out(f"{arch},{s},{m['dcn_bytes']/1e9:.2f},"
                f"{m['max_nic_load']/1e9:.3f},"
                f"{m['max_nic_load']/topo.nic_bw:.2f},"
                f"{m['ici_bytes']/1e9:.1f}")


def _fleet_jobs():
    return [
        JobSpec("big-train", get_config("yi-6b"), SHAPES["train_4k"],
                {"pod": 2, "data": 12, "model": 16}),
        JobSpec("moe-train", get_config("qwen2-moe-a2.7b"),
                SHAPES["train_4k"], {"data": 4, "model": 16}),
        JobSpec("decode", get_config("granite-3-2b"), SHAPES["decode_32k"],
                {"data": 4, "model": 16}),
    ]


def scenario_b(out=print, sim_scale: float = 1.0):
    out("# scenario B: multi-job fleet on 2 pods "
        "(384-chip job spans pods + side jobs)")
    out("strategy,max_nic_GBps,nic_oversub,total_dcn_GBps,sim_wait_ms")
    topo = tpu_topology(n_pods=2)
    for s in STRATS:
        placement, graphs = place_jobs(_fleet_jobs(), topo, strategy=s)
        m = fleet_nic_load(placement, graphs, topo)
        # queueing simulation with TPU constants: one training step's
        # collective messages through the ICI/NIC servers
        res = simulate(graphs, placement, topo, count_scale=sim_scale)
        out(f"{s},{m['max_nic_load']/1e9:.3f},"
            f"{m['max_nic_load']/topo.nic_bw:.2f},"
            f"{m['total_dcn_bytes']/1e9:.1f},{res.total_wait_ms:.4g}")


def main():
    enable_compile_cache()
    scenario_a()
    scenario_b()


if __name__ == "__main__":
    main()
