"""The benchmark's own tests: a sound run is correct, a broken one is not.

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/tests

Each run skips the look for a chip and drives the rest of a run of the
cell — set-up, a window of a few units, the reference check — on the
CPU, with the simulator's answers broken underneath the timed path:

* ``altered``: every answer altered where it is produced (each job's
  wait and finish 1e-6 relative off);
* ``half_batch``: half of every batch left out — the second half of the
  candidates scored gets the first candidate's answers;
* ``control``: the program's float32 Pallas scan (interpreted here) in
  place of its float64 one, at a test size.

It also reduces the small recorded trace kept in ``bench/data``.
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [os.path.join(os.path.dirname(BENCH), "src"), BENCH]

from harness.core import run_cell  # noqa: E402

# (cell, units): enough units that the cell's batch calls occur
CELLS = [("paper_t4.resubmit", 30),
         ("paper_t4.reclock_full", 1), ("paper_t4.search", 1)]


@pytest.fixture(autouse=True)
def device_path(monkeypatch):
    """The float64 ``jax`` scan the chip runs (on XLA:CPU here), not the
    numpy path ``auto`` picks on a CPU."""
    monkeypatch.setenv("REPRO_SIM_BACKEND", "jax")


def _run(cell: str, units: int, fault=None, seed: int = 2147483711) -> dict:
    args = argparse.Namespace(workload=cell, seed=seed, seconds=0.0, trace=0,
                              rehearse=False, units=units)
    return run_cell(args, time.perf_counter(), fault=fault, need_chip=False)


def altered(entry, rows):
    return [dataclasses.replace(
        r, per_job_wait={j: w * (1 + 1e-6) for j, w in r.per_job_wait.items()},
        job_finish={j: f * (1 + 1e-6) for j, f in r.job_finish.items()}) for r in rows]


def half_batch(entry, rows):
    if not entry.endswith("batch") or len(rows) < 2:
        return rows
    half = len(rows) // 2
    return rows[:half] + [rows[0]] * (len(rows) - half)


@pytest.mark.parametrize("cell,units", CELLS)
def test_sound_run_is_correct(cell, units):
    out = _run(cell, units)
    assert out["correct"], out["check"]
    assert out["attempted"] == units


@pytest.mark.parametrize("cell,units", CELLS)
def test_altered_answers_are_caught(cell, units):
    out = _run(cell, units, fault=altered)
    assert not out["correct"], out["check"]


@pytest.mark.parametrize("cell,units", [c for c in CELLS if "reclock" not in c[0]])
def test_half_batch_is_caught(cell, units):
    seen = []

    def fault(entry, rows):
        seen.append(len(rows))
        return half_batch(entry, rows)

    out = _run(cell, units, fault=fault)
    assert max(seen) > 1, "no batch call in the window"
    assert not out["correct"], out["check"]


def test_control_fails_at_test_size(monkeypatch):
    monkeypatch.setenv("REPRO_SIM_BACKEND", "pallas")  # float32, interpreted here
    out = _run("paper_t4.resubmit", 4)
    assert any(n["value"] > n["limit"] for k, n in out["check"].items() if k.endswith("_err"))
    assert not out["correct"]


def test_recorded_trace_reduces():
    from harness.trace import reduce
    red = reduce(os.path.join(BENCH, "data", "window.xplane.pb.gz"))
    assert red.chips == 1 and red.n_ops > 0
    assert 0 < red.busy_s <= red.window_s
    assert 0 < red.scan_s <= red.window_s
    assert red.device_ops and red.idle_gaps
