"""One budgeted placement search per unit.

Each unit is one ``search_placement`` over whole cycles of the mix on an
empty cluster, with a fresh move stream from the seed.
"""
from __future__ import annotations

from harness.traffic import Generator, placement_faults


class Traffic(Generator):

    def prepare(self) -> dict:
        from repro.core.mapping import ONE_SHOT_STRATEGIES
        from repro.search import optimizer  # noqa: F401  (wrapped by the probe)
        self.order = self.whole_cycles()
        self.jobs = [self.new_job(i) for i in self.order]
        self.warm_rows = (len(ONE_SHOT_STRATEGIES), int(self.t["population"]))
        self.found: list = []
        return {"jobs": len(self.jobs), "budget": int(self.t["budget"]),
                "population": int(self.t["population"])}

    def warm_elements(self) -> int:
        from repro.search.optimizer import auto_objective_scale
        return self.stage0_messages(self.order, auto_objective_scale(self.jobs))

    def unit(self) -> str:
        from repro.search import optimizer
        res = optimizer.search_placement(
            self.jobs, self.cluster, None, seed=self.cfg["strategy"],
            budget=int(self.t["budget"]), population=int(self.t["population"]),
            rng_seed=int(self.rng.integers(2**63)))
        if self.probe.recording:
            self.found.append(res.placement.assignments)
        return "search"

    def validity(self) -> dict:
        faults = sum(placement_faults(p, self.row_of, self.n_cores) for p in self.found)
        return {"placement_faults": faults, "info": f"placements_checked={len(self.found)}"}

    def release(self) -> None:
        self.jobs = None
