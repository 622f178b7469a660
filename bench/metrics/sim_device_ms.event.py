"""Host self time of the simulator's ``sim.device`` spans per simulator
call (ms), from the program's own spans (``repro.obs.host_summary``).

This is host-clock time: the host→device copy, the dispatch, the
blocking wait on the chip and the copy back. The chip's own busy time
is read from the device trace (``device_idle_pct``, ``scan_roofline``)."""
from harness.spans import phase_ms


def read(run):
    return phase_ms(run, "sim.device")
