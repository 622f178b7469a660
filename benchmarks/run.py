"""Benchmark driver: one section per paper table/figure + the TPU-side
roofline and mapping benchmarks. ``python -m benchmarks.run``
"""
from __future__ import annotations

import sys
import time

from repro.compile_cache import enable_compile_cache


def main() -> None:
    enable_compile_cache()
    sections = []

    def section(title):
        print(f"\n{'='*72}\n== {title}\n{'='*72}")
        sections.append((title, time.time()))

    from benchmarks import paper_tables
    section("Paper Fig.2 — waiting time, synthetic workloads (B/C/D/N)")
    paper_tables.run("wait_ms", real=False)
    section("Paper Fig.3 — workload finish time, synthetic workloads")
    paper_tables.run("finish_s", real=False)
    section("Paper Fig.4 — total job finish time, synthetic workloads")
    paper_tables.run("job_finish_s", real=False)
    section("Paper Fig.5 — waiting time, real (NPB) workloads")
    paper_tables.run("wait_ms", real=True)

    from benchmarks import meshplan_bench
    section("Mapping-on-TPU A — single pod-spanning job, NIC contention")
    meshplan_bench.scenario_a()
    section("Mapping-on-TPU B — multi-job fleet + queueing simulation")
    meshplan_bench.scenario_b()

    import os
    from benchmarks import roofline
    section("Roofline — single-pod mesh, paper-faithful baseline cells")
    rows = roofline.run("single")
    section("Roofline — multi-pod mesh (pod axis proof)")
    roofline.run("multi")
    if os.path.isdir(roofline.OPT_DIR):
        section("Roofline — baseline vs optimized (dominant term per cell)")
        roofline.run_compare("single")
    if not rows:
        print("NOTE: no dry-run artifacts found; run "
              "`python -m repro.launch.dryrun` first.", file=sys.stderr)

    print(f"\n== done: {len(sections)} sections ==")


if __name__ == "__main__":
    main()
