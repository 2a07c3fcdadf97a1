"""Reduce a ``jax.profiler`` trace (``.xplane.pb``) to the benchmark's
device numbers.

Read with ``jax.profiler.ProfileData`` alone. The window is the host
span ``bench.window`` that the harness opens around the traced ticks;
everything is clipped to it.

* busy: the union of the intervals in which an operation runs on a
  device (the ``XLA Ops`` line of each ``/device:TPU:N`` plane),
  averaged over the devices;
* per-op totals, by the op names the trace gives (kernels by their
  Pallas names, ``copy.N``, ``fusion.N`` ...), leaving out loops and
  calls, whose events enclose the ops of their bodies;
* per-program executions (the ``XLA Modules`` line), by module name;
* idle gaps between device ops, each named by the innermost ``bench.*``
  host span that covers its middle.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

WINDOW_SPAN = "bench.window"
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
_SUFFIX = re.compile(r"\.\d+$")
#: control-flow ops whose events enclose their bodies' ops: busy, but
#: not an op of their own in the per-op totals
_CONTAINERS = ("while", "conditional", "call")


def op_name(event_name: str) -> str:
    """``%fusion.3 = f32[...] fusion(...)`` → ``fusion.3``."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


@dataclasses.dataclass
class TraceSummary:
    window_ns: float
    busy_ns: float                          # averaged over devices
    devices: int
    op_ns: Dict[str, float]                 # op name -> total in window
    op_calls: Dict[str, int]
    module_ns: Dict[str, List[float]]       # module -> execution times
    gaps: List[Tuple[float, str]]           # 50 longest: (ns, host span)

    def kernel_ns(self, kernel: str) -> Optional[float]:
        """Summed device time of ops named ``kernel`` or ``kernel.N``;
        None when the trace holds none."""
        hits = [t for name, t in self.op_ns.items()
                if _SUFFIX.sub("", name) == kernel]
        return sum(hits) if hits else None

    def kernel_calls(self, kernel: str) -> int:
        return sum(c for name, c in self.op_calls.items()
                   if _SUFFIX.sub("", name) == kernel)

    def module_times(self, prefix: str) -> List[float]:
        return [t for name, ts in self.module_ns.items()
                if name.split("(")[0] == prefix for t in ts]


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _union(intervals):
    total, cur_s, cur_e = 0.0, None, None
    merged = []
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                merged.append((cur_s, cur_e))
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        merged.append((cur_s, cur_e))
    for s, e in merged:
        total += e - s
    return total, merged


def reduce_trace(path: str, window_span: str = WINDOW_SPAN) -> TraceSummary:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    spans = []                               # (start, end, name)
    devices = []
    for plane in data.planes:
        if _DEVICE_PLANE.match(plane.name):
            devices.append(plane)
            continue
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("bench."):
                    spans.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                  ev.name))
    windows = [s for s in spans if s[2] == window_span]
    if not windows:
        raise ValueError(f"trace has no {window_span!r} span")
    w0, w1 = windows[0][0], windows[0][1]
    if not devices:
        raise ValueError("trace has no TPU device plane")

    op_ns: Dict[str, float] = defaultdict(float)
    op_calls: Dict[str, int] = defaultdict(int)
    module_ns: Dict[str, List[float]] = defaultdict(list)
    busy_total = 0.0
    gaps = []
    for plane in devices:
        intervals = []
        for line in plane.lines:
            if line.name not in ("XLA Ops", "XLA Modules"):
                continue
            for ev in line.events:
                s = max(ev.start_ns, w0)
                e = min(ev.start_ns + ev.duration_ns, w1)
                if e <= s:
                    continue
                if line.name == "XLA Modules":
                    module_ns[ev.name].append(e - s)
                    continue
                intervals.append((s, e))
                name = op_name(ev.name)
                if _SUFFIX.sub("", name) in _CONTAINERS:
                    continue
                op_ns[name] += e - s
                op_calls[name] += 1
        busy, merged = _union(intervals)
        busy_total += busy
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append((b - a, (a + b) / 2))
    gaps.sort(reverse=True)
    gaps = [(ns, _host_span(spans, mid)) for ns, mid in gaps[:50]]
    return TraceSummary(
        window_ns=w1 - w0, busy_ns=busy_total / len(devices),
        devices=len(devices), op_ns=dict(op_ns), op_calls=dict(op_calls),
        module_ns=dict(module_ns), gaps=gaps,
    )


def _host_span(spans, t: float) -> str:
    """Innermost ``bench.*`` span (other than the window) covering t."""
    best = None
    for s, e, name in spans:
        if name != WINDOW_SPAN and s <= t <= e:
            if best is None or e - s < best[1] - best[0]:
                best = (s, e, name)
    return best[2] if best else "outside bench spans"


def breakdown(summary: TraceSummary, top: int = 10) -> Dict[str, list]:
    """Top device ops by time and the longest idle gaps, in seconds."""
    ops = sorted(summary.op_ns.items(), key=lambda kv: -kv[1])[:top]
    return {
        "device_ops": [[name, ns * 1e-9] for name, ns in ops],
        "idle_gaps": [[name, ns * 1e-9] for ns, name in summary.gaps[:top]],
    }
