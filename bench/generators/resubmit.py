"""Closed loop through ``FleetScheduler``: every departure is resubmitted.

The cluster is filled as the seed's symmetric image of one fixed
placement (``Generator.pinned_fill``), the jobs submitted in an order
drawn from the seed. Every job that departs is resubmitted at once
under a new id with the same template: a user's next run of the same
application. A remap pass is put on the scheduler's event queue after
every ``remap_every`` admit and departure events (a rebalancer driven
by fleet churn), so the share of remap passes among events does not
follow the simulated clock, which the seed's placements speed up or
slow down. The scheduler's own settings are the traffic file's
``scheduler`` object. A unit is one scheduler event (superseded
departure events popped on the way count in the unit after them).
"""
from __future__ import annotations

from harness.traffic import Generator, placement_faults, scheduler_config


class Traffic(Generator):

    def prepare(self) -> dict:
        from repro.sched import FleetScheduler
        jobs, strategy = self.pinned_fill()
        self.order = [self.template_of[j.job_id] for j in jobs]
        config = scheduler_config(self.t["scheduler"], self.rng)
        self.count_scale = config.count_scale
        self.warm_rows = (1, config.remap.candidates)
        self.sched = FleetScheduler(self.cluster, strategy, config=config)
        for job in jobs:
            self.sched.submit(job, at=0.0)
        while len(self.sched.live) < len(self.order):
            self.sched.step()
        self.snaps: list[dict] = []
        self.churn = 0
        return {"fill_jobs": len(self.order),
                "fill_cores": sum(int(self.mix[i]["procs"]) for i in self.order)}

    def warm_elements(self) -> int:
        return self.stage0_messages(self.order, self.count_scale)

    def unit(self) -> str:
        from repro.sched.events import DEPARTURE, REMAP, Event
        s = self.sched
        while True:
            ev = s.events.peek()
            was_live = ev.kind == DEPARTURE and ev.job_id in s.live
            with self.probe.span(f"event.{ev.kind}"):
                s.step()
            if ev.kind != DEPARTURE:
                break
            if was_live and ev.job_id not in s.live:
                # a user's next run of the same application
                s.submit(self.new_job(self.template_of[ev.job_id]), at=s.now)
                break
        if ev.kind != REMAP:
            self.churn += 1
            if self.churn % int(self.t["remap_every"]) == 0:
                s.events.push(Event(time=s.now, kind=REMAP))
        if self.probe.recording:
            self.snaps.append(dict(s.placement.assignments))
        return ev.kind

    def validity(self) -> dict:
        faults = sum(placement_faults(snap, self.row_of, self.n_cores) for snap in self.snaps)
        try:
            self.sched.check_invariants()
        except Exception:
            faults += 1
        commits = sum(1 for d in self.sched.decisions if d.committed)
        return {"placement_faults": faults,
                "info": f"placements_checked={len(self.snaps)} remap_passes_decided="
                        f"{len(self.sched.decisions)} remap_commits={commits}"}

    def release(self) -> None:
        self.sched = None
