"""Host self time of the simulator's ``sim.order`` spans per simulator
call (ms), from the program's own spans (``repro.obs.host_summary``)."""
from harness.spans import phase_ms


def read(run):
    return phase_ms(run, "sim.order")
