"""Host time per event outside simulator calls (ms): the scheduler's own
engines (clock, admission, remap) and the resubmission, from the
benchmark's wrappers around ``repro.core.simulator``."""


def read(run):
    if not run.units:
        return None
    return sum(u.wall_s - u.sim_s for u in run.units) * 1e3 / len(run.units)
