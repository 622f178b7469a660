"""Bytes the simulator sent to the device per simulator call (MB): the
count of its ``sim.device`` spans (``repro.obs.host_summary``)."""
from harness.spans import h2d_mb


def read(run):
    return h2d_mb(run)
