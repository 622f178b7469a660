"""Paper reproduction benchmarks — one per paper figure.

Fig. 2: waiting time of messages, synthetic workloads 1-4, B/C/D/N.
Fig. 3: workload finish time, synthetic workloads.
Fig. 4: total finish time of parallel jobs, synthetic workloads.
Fig. 5: waiting time of messages, real (NPB) workloads 1-4.

``count_scale`` trades fidelity for wall time; 0.2 keeps every strategy
ordering of the full tables (verified against 1.0 on workloads 1 and 4)
while fitting the CI budget.
"""
from __future__ import annotations

import time

from repro.compile_cache import enable_compile_cache
from repro.core import ClusterTopology, STRATEGIES, simulate
from repro.core.workloads import REAL, SYNTHETIC

ORDER = ("blocked", "cyclic", "drb", "new")


def _bench(workloads: dict, metric: str, count_scale: float):
    rows = []
    cluster = ClusterTopology()
    for wl_name, fn in workloads.items():
        jobs = fn()
        vals = {}
        for sname in ORDER:
            t0 = time.time()
            placement = STRATEGIES[sname](jobs, cluster)
            res = simulate(jobs, placement, count_scale=count_scale)
            vals[sname] = {
                "wait_ms": res.total_wait_ms,
                "finish_s": res.workload_finish,
                "job_finish_s": res.total_job_finish,
            }[metric]
            vals[f"_{sname}_runtime"] = time.time() - t0
        best_other = min(vals[s] for s in ORDER if s != "new")
        gain = (1 - vals["new"] / best_other) * 100 if best_other else 0.0
        rows.append((wl_name, vals, gain))
    return rows


def run(metric: str = "wait_ms", real: bool = False,
        count_scale: float = 0.2, out=print):
    workloads = REAL if real else SYNTHETIC
    fig = {"wait_ms": ("fig5" if real else "fig2"),
           "finish_s": "fig3", "job_finish_s": "fig4"}[metric]
    out(f"# paper {fig}: {'real' if real else 'synthetic'} workloads, "
        f"metric={metric}, count_scale={count_scale}")
    out("workload,blocked,cyclic,drb,new,gain_vs_best_other_pct")
    for wl_name, vals, gain in _bench(workloads, metric, count_scale):
        out(f"{wl_name},{vals['blocked']:.4g},{vals['cyclic']:.4g},"
            f"{vals['drb']:.4g},{vals['new']:.4g},{gain:+.1f}")


def main():
    enable_compile_cache()
    run("wait_ms", real=False)
    run("finish_s", real=False)
    run("job_finish_s", real=False)
    run("wait_ms", real=True)


if __name__ == "__main__":
    main()
