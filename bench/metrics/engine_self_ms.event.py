"""Host self time of the scheduler's own spans (``sched.*``: each
event's root, admission, re-clock, remap pass) per event (ms), from the
program's own spans (``repro.obs.host_summary``). Unlike
``engine_ms.event`` it leaves out the generator's work between events."""
from harness.spans import self_ms


def read(run):
    return self_ms(run, lambda name: name.startswith("sched."), len(run.units))
