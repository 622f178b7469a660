"""Readers of the program's own host-clock spans (``repro.obs``).

The program keeps every span it opens in an always-on ring and totals
them per name with ``repro.obs.host_summary(t0, t1)``. A reader here
takes the measured window, from the first unit's start to the last
unit's end, and returns ``None``, never raising, when the program has no
``host_summary`` (a program without these spans) or when its ring no
longer reaches back to the window's start.
"""
from __future__ import annotations


def window_summary(run):
    """Per-name span totals of the window, or ``None``."""
    from repro import obs
    summary = getattr(obs, "host_summary", None)
    if summary is None or not run.units:
        return None
    return summary(run.units[0].t0, run.units[-1].t1)


def self_ms(run, match, per: int):
    """Summed self time (ms) of the window's spans whose name ``match``
    accepts, over ``per``; ``None`` when there is nothing to read."""
    totals = window_summary(run)
    if totals is None or not per:
        return None
    return sum(t.self_s for name, t in totals.items() if match(name)) * 1e3 / per


def phase_ms(run, name: str):
    """Self time (ms) of the simulator phase ``name`` per outermost
    simulator call, the denominator of ``sim_call_ms``."""
    return self_ms(run, name.__eq__, len(run.calls))


def h2d_mb(run):
    """Bytes sent to the device (``sim.device`` spans' count), in MB
    per outermost simulator call."""
    totals = window_summary(run)
    if totals is None or not run.calls:
        return None
    device = totals.get("sim.device")
    return (device.count if device else 0) / 1e6 / len(run.calls)
