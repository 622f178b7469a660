"""The benchmark's wrappers around the simulator's entry points.

Every call into ``repro.core.simulator`` (``simulate``, ``simulate_batch``
and the two ``SimHandle`` methods) goes through :class:`Probe` once it is
installed. For each outermost call it records the host wall time, the
job ids and a copy of the placements it was given (the inputs the
reference later re-runs), and the answers it returned. With tracing on,
each call is also a host span in the profiler's trace, named
``sim.<entry>``, so idle gaps on the device can be attributed.

The probe also counts the device programs JAX builds (compiled or
loaded from the persistent cache) from JAX's own monitoring events.
"""
from __future__ import annotations

import contextlib
import dataclasses
import inspect
import sys
import time


@dataclasses.dataclass
class Call:
    entry: str
    t0: float
    t1: float
    job_ids: list
    placements: list          # one {job_id: cores} per row
    count_scale: float
    results: list             # the program's answers, one per row

    @property
    def wall_s(self) -> float:
        return self.t1 - self.t0

    @property
    def rows(self) -> int:
        return len(self.placements)


class CompileCounter:
    """Device programs JAX builds, from its monitoring events. Every
    program a jit call obtains fires one backend-compile duration event,
    whether it is compiled or loaded from the persistent cache; cache
    loads also fire a cache-hit event. One per process (:meth:`get`):
    JAX keeps its listeners for good."""

    _one = None

    @classmethod
    def get(cls) -> "CompileCounter":
        if cls._one is None:
            cls._one = cls()
        return cls._one

    def __init__(self):
        import jax
        self.compiles = 0
        self.cache_hits = 0
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    @property
    def programs(self) -> int:
        return self.compiles


class Probe:
    """Installs recording wrappers on the simulator's entry points."""

    ENTRIES = ("simulate", "simulate_batch")

    def __init__(self):
        self.calls: list[Call] = []
        self.recording = False
        self.tracing = False
        self.fault = None         # tests: callable(entry, results) -> results
        self._depth = 0
        self._undo: list = []

    def install(self) -> "Probe":
        from repro.core import simulator
        for name in self.ENTRIES:
            orig = getattr(simulator, name)
            wrapped = self._wrap(f"sim.{name}", orig, handle=False)
            for mod in list(sys.modules.values()):
                if getattr(mod, "__name__", "").startswith("repro") \
                        and getattr(mod, name, None) is orig:
                    self._undo.append((mod, name, orig))
                    setattr(mod, name, wrapped)
            orig_m = getattr(simulator.SimHandle, name)
            self._undo.append((simulator.SimHandle, name, orig_m))
            setattr(simulator.SimHandle, name,
                    self._wrap(f"sim.SimHandle.{name}", orig_m, handle=True))
        return self

    def uninstall(self) -> None:
        for owner, name, orig in reversed(self._undo):
            setattr(owner, name, orig)
        self._undo.clear()

    def span(self, name: str):
        if not self.tracing:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def _wrap(self, entry: str, fn, handle: bool):
        sig = inspect.signature(fn)
        batched = entry.endswith("batch")
        probe = self

        def wrapper(*args, **kwargs):
            if probe._depth:
                return fn(*args, **kwargs)
            a = sig.bind(*args, **kwargs).arguments
            jobs = list(a["jobs"])
            job_ids = [g.job_id for g in jobs]
            pls = list(a["placements"]) if batched else [a["placement"]]
            snaps = [{j: p.assignments[j] for j in job_ids} for p in pls]
            scale = a["self"].count_scale if handle \
                else a.get("count_scale", sig.parameters["count_scale"].default)
            probe._depth += 1
            try:
                with probe.span(entry):
                    t0 = time.perf_counter()
                    out = fn(*args, **kwargs)
                    t1 = time.perf_counter()
            finally:
                probe._depth -= 1
            rows = list(out) if batched else [out]
            if probe.fault is not None:
                rows = probe.fault(entry, rows)
                out = rows if batched else rows[0]
            if probe.recording:
                probe.calls.append(Call(entry, t0, t1, job_ids, snaps,
                                        float(scale), rows))
            return out

        wrapper.__wrapped__ = fn
        return wrapper
