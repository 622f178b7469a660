"""The readers of the program's own host spans (``harness/spans.py``).

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/tests

A few units of each cell run on the CPU (the chip's float64 ``jax``
scan on XLA:CPU), and every per-layer metric that reads the program's
spans is read from the run's record. Against a program without
``repro.obs.host_summary``, or with a ring that no longer covers the
window, every such reader returns ``None``.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [os.path.join(os.path.dirname(BENCH), "src"), BENCH]

from harness import core  # noqa: E402

SPAN_METRICS = {
    "event": ["sim_flatten_ms.event", "sim_route_ms.event", "sim_order_ms.event",
              "sim_pack_ms.event", "sim_device_ms.event", "sim_reduce_ms.event",
              "sim_h2d_mb.event", "engine_self_ms.event"],
    "score": ["sim_route_ms.score", "sim_order_ms.score", "sim_pack_ms.score",
              "sim_device_ms.score", "sim_reduce_ms.score", "sim_h2d_mb.score",
              "search_self_ms.score"],
}
CELLS = [("paper_t4.resubmit", 20), ("paper_t4.reclock_full", 1), ("paper_t4.search", 1)]
_RUNS: dict = {}


@pytest.fixture(autouse=True)
def device_path(monkeypatch):
    monkeypatch.setenv("REPRO_SIM_BACKEND", "jax")


def _suffix(cell: str) -> str:
    return "event" if cell.endswith("resubmit") else "score"


def _run(cell: str, units: int, monkeypatch) -> core.Run:
    """The record the metric readers see of a short CPU run of ``cell``."""
    if cell not in _RUNS:
        orig = core.reader

        def spy(name):
            read = orig(name)

            def keep(run):
                _RUNS[cell] = run
                return read(run)
            return keep

        monkeypatch.setattr(core, "reader", spy)
        args = argparse.Namespace(workload=cell, seed=3100000001, seconds=0.0, trace=0,
                                  rehearse=False, units=units)
        out = core.run_cell(args, time.perf_counter(), need_chip=False)
        assert out["correct"], out["check"]
        monkeypatch.setattr(core, "reader", orig)
    return _RUNS[cell]


def _listed(cell: str) -> list[str]:
    spec, c, _, _ = core.load_spec(cell)
    _, layer = core.cell_metrics(spec, c)
    return sorted(m["name"] for m in layer if m["source"] in ("program_span", "program_counter")
                  and m["name"] not in ("engine_ms.event", "sim_call_ms.event",
                                        "sim_call_ms.score", "window_compiles.event",
                                        "window_compiles.score"))


@pytest.mark.parametrize("cell,units", CELLS)
def test_every_span_metric_reads_a_value(cell, units, monkeypatch):
    run = _run(cell, units, monkeypatch)
    names = _listed(cell)
    want = [m for m in SPAN_METRICS[_suffix(cell)]
            if cell.endswith("search") or not m.startswith("search_")]
    assert names == sorted(want)
    for name in names:
        value = core.reader(name)(run)
        assert value is not None and value >= 0, name
    assert core.reader(f"sim_device_ms.{_suffix(cell)}")(run) > 0
    assert core.reader(f"sim_h2d_mb.{_suffix(cell)}")(run) > 0


@pytest.mark.parametrize("cell,units", CELLS)
def test_phases_sum_within_simulator_call(cell, units, monkeypatch):
    run = _run(cell, units, monkeypatch)
    suffix = _suffix(cell)
    phases = sum(core.reader(name)(run) for name in SPAN_METRICS[suffix]
                 if name.startswith("sim_") and "_ms." in name)
    call = core.reader(f"sim_call_ms.{suffix}")(run)
    assert 0 < phases <= call
    if suffix == "event":
        assert core.reader("engine_self_ms.event")(run) <= core.reader("engine_ms.event")(run)


@pytest.mark.parametrize("cell,units", CELLS)
def test_readers_none_without_host_summary(cell, units, monkeypatch):
    run = _run(cell, units, monkeypatch)
    from repro import obs
    monkeypatch.delattr(obs, "host_summary")
    for name in _listed(cell):
        assert core.reader(name)(run) is None, name


@pytest.mark.parametrize("cell,units", CELLS)
def test_readers_none_on_overflowed_ring(cell, units, monkeypatch):
    run = _run(cell, units, monkeypatch)
    from repro import obs
    ring = obs.HostSpans(capacity=8)
    for _ in range(9):                  # the ninth drops a record newer than the window
        with ring.span("sim.order"):
            pass
    monkeypatch.setattr(obs, "host_summary", ring.summary)
    for name in _listed(cell):
        assert core.reader(name)(run) is None, name
