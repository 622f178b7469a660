"""A static full snapshot, re-clocked after one move per unit.

Each unit applies one move drawn from ``repro.search.moves.neighbours``
to the snapshot and re-clocks the fleet with one ``simulate``. With
``fill: table`` the snapshot is the same for every seed and the seed
draws only the moves.
"""
from __future__ import annotations

from harness.traffic import Generator, placement_faults


class Traffic(Generator):

    def prepare(self) -> dict:
        from repro.search.moves import SearchState, domain_sizes
        self.order = self.whole_cycles()
        self.jobs, placement, tracker = self.snapshot(self.order)
        self.state = SearchState(self.cluster, {j: c.copy() for j, c in placement.assignments.items()},
                                 tracker.free_mask())
        self.sizes = domain_sizes(self.cluster)
        self.placed: list = []
        return {"jobs": len(self.jobs), "free_cores": int(tracker.total_free())}

    def warm_elements(self) -> int:
        return self.stage0_messages(self.order, float(self.t["count_scale"]))

    def unit(self) -> str:
        from repro.core import simulator
        from repro.search.moves import neighbours
        moves = neighbours(self.rng, self.state, 1, allow_cross_job=False, sizes=self.sizes)
        placement = (moves[0][1] if moves else self.state).placement()
        simulator.simulate(self.jobs, placement, self.cluster,
                           count_scale=float(self.t["count_scale"]))
        if self.probe.recording:
            self.placed.append(placement.assignments)
        return "reclock"

    def validity(self) -> dict:
        faults = sum(placement_faults(p, self.row_of, self.n_cores) for p in self.placed)
        return {"placement_faults": faults, "info": f"placements_checked={len(self.placed)}"}

    def release(self) -> None:
        self.jobs = self.state = None
