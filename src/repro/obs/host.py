"""Host-clock spans: where the host's time goes, always on (DESIGN.md §11).

The sim-time :class:`~repro.obs.recorder.Recorder` says *what* the
scheduler and simulator decided; this module says how long the host
took to decide it. A span is opened with ``with obs.span(name):`` at a
layer boundary (``sched.<kind>`` around each scheduler event,
``sim.call`` around each simulator entry, ``sim.route`` / ``sim.order``
/ ``sim.pack`` / ``sim.device`` / ``sim.reduce`` inside it, ...). Each
span does two things:

* while a JAX profiler session is active it also opens a
  ``jax.profiler.TraceAnnotation`` of the same name, so the span sits on
  the profiler's clock beside the device operations of the same
  ``.xplane.pb``; with no session that half is inert;
* on exit it writes one record into a fixed-size ring of preallocated
  numpy arrays (the flight recorder): name id, start and end on
  ``time.perf_counter``, self time (its duration minus the time its
  child spans cover, kept at exit with a stack of child-time
  accumulators), the enclosing record (``parent``), the outermost one
  (``root``: every span of one scheduler event or one direct simulator
  call shares it) and an optional integer of work done, set inside the
  span with ``count``.

Records are numbered in the order their spans *open*, so start times
rise with the number and a record's ``parent`` and ``root`` are numbers
too. :meth:`HostSpans.summary` totals, per name, the records that
started in a window, and returns ``None`` once the ring has dropped a
record that started inside it.

Cost contract: the ring is always on. A span allocates nothing beyond
the floats of its two clock reads; writes go through memoryviews of the
preallocated arrays. Call sites keep to a few spans per simulator call
(``sim_scan`` holds them to at most 4 + 4 per routing stage whatever
the batch), so the default 2^16 records hold every benchmark window.
Spans never reach the sim-time recorder, whose dumps stay deterministic.
One thread: the scheduler, simulator and search that open spans are
single-threaded.
"""
from __future__ import annotations

import math
from time import perf_counter
from typing import NamedTuple, Optional

import numpy as np

_MAX_DEPTH = 64


def _profiler_active() -> bool:
    """Whether a JAX profiler session is recording (imports JAX once)."""
    global _profiler_active
    from jax.profiler import TraceAnnotation
    _profiler_active = TraceAnnotation.is_enabled
    return _profiler_active()


class SpanTotals(NamedTuple):
    """One name's records in a window: how many, their summed self
    seconds, and their summed ``count``."""

    n: int
    self_s: float
    count: int


class HostSpans:
    """A bounded ring of host-clock span records (a flight recorder).

    ``span(name)`` returns the ring itself as the context manager, so
    opening a span allocates nothing; ``count(n)`` inside it adds ``n``
    to the innermost open span's work count.
    """

    def __init__(self, capacity: int = 1 << 16):
        if capacity <= 0 or capacity & (capacity - 1):
            raise ValueError(f"capacity must be a power of two, got {capacity}")
        self.capacity = capacity
        self._mask = capacity - 1
        self.seq = np.full(capacity, -1, dtype=np.int64)
        self.name_id = np.zeros(capacity, dtype=np.int32)
        self.start = np.zeros(capacity)
        self.end = np.zeros(capacity)
        self.self_s = np.zeros(capacity)
        self.parent = np.full(capacity, -1, dtype=np.int64)
        self.root = np.full(capacity, -1, dtype=np.int64)
        self.counts = np.zeros(capacity, dtype=np.int64)
        self._w = [memoryview(a) for a in (
            self.seq, self.name_id, self.start, self.end, self.self_s,
            self.parent, self.root, self.counts)]
        self.names: list[str] = []
        self._ids: dict[str, int] = {}
        self._n = 0                     # records numbered so far
        self._lost = -math.inf          # latest start of a dropped record
        self._next = 0                  # name id the next __enter__ opens
        # the open spans by depth: [seq, name id, start, child seconds,
        # count, profiler annotation]
        self._depth = 0
        self._open = [[0, 0, 0.0, 0.0, 0, None] for _ in range(_MAX_DEPTH)]

    # -- recording -------------------------------------------------------
    def span(self, name: str) -> "HostSpans":
        nid = self._ids.get(name)
        if nid is None:
            nid = self._ids[name] = len(self.names)
            self.names.append(name)
        self._next = nid
        return self

    def count(self, n: int) -> None:
        """Add ``n`` units of work to the innermost open span."""
        self._open[self._depth - 1][4] += n

    def __enter__(self) -> "HostSpans":
        d = self._depth
        if d == _MAX_DEPTH:
            raise RuntimeError(f"host spans nested deeper than {_MAX_DEPTH}")
        o = self._open[d]
        o[0] = self._n
        self._n += 1
        o[1] = nid = self._next
        o[3] = 0.0
        o[4] = 0
        if _profiler_active():
            from jax.profiler import TraceAnnotation
            o[5] = TraceAnnotation(self.names[nid])
            o[5].__enter__()
        self._depth = d + 1
        o[2] = perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        t1 = perf_counter()
        d = self._depth - 1
        self._depth = d
        stack = self._open
        seq, nid, t0, child, n, ann = stack[d]
        if ann is not None:
            stack[d][5] = None
            ann.__exit__(None, None, None)
        dur = t1 - t0
        if d:
            up = stack[d - 1]
            up[3] += dur
            parent = up[0]
        else:
            parent = -1
        slot = seq & self._mask
        w_seq, w_name, w_start, w_end, w_self, w_parent, w_root, w_count = self._w
        old = w_seq[slot]
        if old > seq:                   # lapped while open: a newer record holds the slot
            self._lost = max(self._lost, t0)
            return False
        if old >= 0 and w_start[slot] > self._lost:
            self._lost = w_start[slot]
        w_seq[slot] = seq
        w_name[slot] = nid
        w_start[slot] = t0
        w_end[slot] = t1
        w_self[slot] = dur - child
        w_parent[slot] = parent
        w_root[slot] = stack[0][0]
        w_count[slot] = n
        return False

    # -- reading ---------------------------------------------------------
    def _held(self, t0: float, t1: float) -> np.ndarray:
        return (self.seq >= 0) & (self.start >= t0) & (self.start <= t1)

    def summary(self, t0: float, t1: float) -> Optional[dict[str, SpanTotals]]:
        """Per name, the records that started in ``[t0, t1]``; ``None``
        when the ring no longer reaches back to ``t0``."""
        if self._lost != -math.inf and self._lost >= t0:
            return None
        m = self._held(t0, t1)
        ids = self.name_id[m]
        k = len(self.names)
        n = np.bincount(ids, minlength=k)
        self_s = np.bincount(ids, weights=self.self_s[m], minlength=k)
        count = np.zeros(k, dtype=np.int64)
        np.add.at(count, ids, self.counts[m])
        return {self.names[i]: SpanTotals(int(n[i]), float(self_s[i]), int(count[i]))
                for i in np.flatnonzero(n)}

    def records(self, t0: float = -math.inf,
                t1: float = math.inf) -> dict[str, np.ndarray]:
        """The records the ring holds that started in ``[t0, t1]``, as
        arrays in opening order (``seq``), with their names."""
        m = self._held(t0, t1)
        order = np.argsort(self.seq[m], kind="stable")
        out = {key: getattr(self, attr)[m][order] for key, attr in (
            ("seq", "seq"), ("start", "start"), ("end", "end"),
            ("self_s", "self_s"), ("parent", "parent"), ("root", "root"),
            ("count", "counts"))}
        out["name"] = np.asarray(self.names, dtype=object)[self.name_id[m][order]] \
            if self.names else np.empty(0, dtype=object)
        return out


#: the process-wide ring every instrumented call site writes to
HOST = HostSpans()
span = HOST.span
host_summary = HOST.summary
host_records = HOST.records
