"""Mean host wall time of one simulator call in the window (ms), taken
from the benchmark's wrappers around ``repro.core.simulator``."""


def read(run):
    if not run.calls:
        return None
    return sum(c.wall_s for c in run.calls) * 1e3 / len(run.calls)
