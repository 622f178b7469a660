"""Reduce a JAX profiler trace of the measured window to numbers.

Reads the ``.xplane.pb`` that ``jax.profiler.trace`` writes with
``jax.profiler.ProfileData`` alone. On every ``/device:TPU:<n>`` plane:

* busy time is the union of the intervals of the ``XLA Ops`` line,
  clipped to the window (the host span ``bench.window``);
* scan time is the summed duration of the ``XLA Modules`` events whose
  program name contains ``scan`` (the simulator's jitted ``scan`` and the
  ``lindley_scan`` kernel), clipped likewise;
* the breakdown lists the device operations that took most time (by
  operation name, numeric suffixes dropped), and the idle gaps of the
  device labelled by the innermost benchmark host span (``bench.*``,
  ``event.*``, ``sim.*``) covering each gap's midpoint.

Busy and scan times are averaged over the chips the run used.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re

HOST_SPAN = re.compile(r"^(bench|event|sim)\.")
OP_NAME = re.compile(r"^%?([A-Za-z_\-]+?)(?:[.\d]*)(?:\s|=|$)")


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float
    scan_s: float
    device_ops: list
    idle_gaps: list
    chips: int
    n_ops: int


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def op_kind(name: str) -> str:
    m = OP_NAME.match(name)
    return m.group(1) if m else name.split()[0]


def find_trace(log_dir: str) -> str:
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(files, key=os.path.getmtime)


def reduce(path: str) -> Reduction:
    """Reduce one ``.xplane.pb`` (or a gzipped ``.xplane.pb.gz``)."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        import gzip
        with gzip.open(path, "rb") as f:
            prof = ProfileData.from_serialized_xspace(f.read())
    else:
        prof = ProfileData.from_file(path)
    spans: list[tuple[float, float, str]] = []
    window = None
    devices = []
    for plane in prof.planes:
        if plane.name.startswith("/device:TPU:"):
            devices.append(plane)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == "bench.window":
                        window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                    elif HOST_SPAN.match(ev.name):
                        spans.append((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name))
    if window is None:
        raise ValueError("trace has no bench.window span")
    if not devices:
        raise ValueError("trace has no TPU device plane")
    w0, w1 = window
    busy = scan = 0.0
    n_ops = 0
    kinds: collections.Counter = collections.Counter()
    gaps: collections.Counter = collections.Counter()
    spans.sort(key=lambda s: (s[0], -(s[1] - s[0])))
    for plane in devices:
        ops = []
        for line in plane.lines:
            if line.name == "XLA Ops":
                for ev in line.events:
                    a, b = max(ev.start_ns, w0), min(ev.start_ns + ev.duration_ns, w1)
                    if b > a:
                        ops.append((a, b))
                        kinds[op_kind(ev.name)] += (b - a) * 1e-9
            elif line.name == "XLA Modules":
                for ev in line.events:
                    a, b = max(ev.start_ns, w0), min(ev.start_ns + ev.duration_ns, w1)
                    if b > a and "scan" in ev.name.lower():
                        scan += (b - a) * 1e-9
        n_ops += len(ops)
        merged = _union(ops)
        busy += sum(b - a for a, b in merged) * 1e-9
        edges = [w0] + [x for ab in merged for x in ab] + [w1]
        idle = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
        for (a, b), name in zip(idle, _labels([(a + b) / 2 for a, b in idle], spans)):
            gaps[name] += (b - a) * 1e-9
    chips = len(devices)
    return Reduction(
        window_s=(w1 - w0) * 1e-9, busy_s=busy / chips, scan_s=scan / chips,
        device_ops=[[k, v / chips] for k, v in kinds.most_common(10)],
        idle_gaps=[[k, v / chips] for k, v in gaps.most_common(10)],
        chips=chips, n_ops=n_ops)


def _labels(times: list[float], spans: list) -> list[str]:
    """Innermost benchmark span covering each of the ascending ``times``.

    Host spans of one thread nest, so one sweep with a stack of open
    spans (sorted by start, longest first) finds them."""
    out, stack, i = [], [], 0
    for t in times:
        while i < len(spans) and spans[i][0] <= t:
            while stack and stack[-1][1] < spans[i][0]:
                stack.pop()
            stack.append(spans[i])
            i += 1
        while stack and stack[-1][1] < t:
            stack.pop()
        out.append(stack[-1][2] if stack else "outside host spans")
    return out
