"""Reduce a ``jax.profiler`` trace (``.xplane.pb``) by the program's own
names: device time of the decode step by named scope, and the engine's
``serve.*`` host spans with the device idle time under them.

``trace_reduce`` names device ops by HLO name alone. The program's
scopes (``jax.named_scope``: ``attention``, ``attention/page_write``,
``attention/select``, ``attention/gather``, ``mlp``, ``head``) reach the
trace as each op's ``tf_op`` stat, its JAX name path, e.g.
``jit(decode_step)/while/body/closed_call/attention/select/.../pallas_call``.
``jax.profiler.ProfileData`` does not expose that stat, so it is read
here from the TPU plane's event metadata with a minimal protobuf wire
reader (XSpace → XPlane → event metadata → XStat), keyed by the
program and the op's full event name, and joined to ``ProfileData``'s
op events by that name.

As in ``trace_reduce``, everything is clipped to the host span
``bench.window`` and loops and calls (whose events enclose their
bodies' ops) are not ops of their own. Within each execution of the
decode module, each op goes to the innermost program scope among the
components of its ``tf_op`` path (its last component, the op itself,
excluded); an op with none goes to ``unscoped``. Device idle time
(the window less the union of op intervals) is given to the innermost
``bench.*`` or ``serve.*`` host span over it.
"""

from __future__ import annotations

import dataclasses
import functools
import os
import re
from collections import defaultdict
from typing import Dict, Iterator, List, Optional, Tuple

from bench import harness, trace_reduce
from bench.program import DECODE_MODULE

#: the program's named scopes, as paths of scope names
SCOPES = ("attention", "attention/page_write", "attention/select",
          "attention/gather", "mlp", "head")
UNSCOPED = "unscoped"
#: an op whose name has two ``tf_op`` paths in one program
AMBIGUOUS = "ambiguous"
_MODULE_ID = re.compile(r"^(.*)\((\d+)\)$")
_SCOPE_PARTS = [(s, tuple(s.split("/"))) for s in SCOPES]


# --------------------------------------------------------------------------
# the protobuf wire format, as far as the event metadata needs it
# --------------------------------------------------------------------------

def _varint(buf, i: int) -> Tuple[int, int]:
    shift = value = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf) -> Iterator[Tuple[int, object]]:
    """``(field number, value)`` of each field of a serialized message:
    varints as ints, length-delimited fields as memoryviews, fixed-width
    fields as raw bytes."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == 1:
            value, i = bytes(buf[i:i + 8]), i + 8
        elif wire == 5:
            value, i = bytes(buf[i:i + 4]), i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, value


def _map_values(entries) -> Iterator[object]:
    """Values of a protobuf map's entries (key 1, value 2)."""
    for entry in entries:
        for f, v in _fields(entry):
            if f == 2:
                yield v


def read_tf_ops(path: str) -> Dict[Tuple[str, str], List[str]]:
    """``(program id, full op name) -> tf_op paths`` from the event
    metadata of every TPU plane (XPlane: name 2, event_metadata 4,
    stat_metadata 5; XEventMetadata: name 2, stats 5; XStat:
    metadata_id 1, uint64 3, int64 4, str 5, ref 7). The ``:type``
    suffix of a ``tf_op`` is dropped."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out: Dict[Tuple[str, str], List[str]] = defaultdict(list)
    for f, plane in _fields(space):
        if f != 1:
            continue
        name, events, stats = None, [], []
        for pf, v in _fields(plane):
            if pf == 2:
                name = bytes(v).decode()
                if not trace_reduce._DEVICE_PLANE.match(name):
                    break
            elif pf == 4:
                events.append(v)
            elif pf == 5:
                stats.append(v)
        if name is None or not trace_reduce._DEVICE_PLANE.match(name):
            continue
        stat_names = {}
        for meta in _map_values(stats):
            fields = dict(_fields(meta))
            stat_names[fields.get(1, 0)] = bytes(fields.get(2, b"")).decode()
        for meta in _map_values(events):
            op, program, tf_op = None, "", ""
            for ef, v in _fields(meta):
                if ef == 2:
                    op = bytes(v).decode()
                elif ef == 5:
                    stat = dict(_fields(v))
                    kind = stat_names.get(stat.get(1))
                    if kind == "program_id":
                        program = str(stat.get(3, stat.get(4, "")))
                    elif kind == "tf_op":
                        tf_op = (bytes(stat[5]).decode() if 5 in stat
                                 else stat_names.get(stat.get(7), ""))
            if op is not None:
                tf_op = tf_op.rsplit(":", 1)[0]
                if tf_op not in out[(program, op)]:
                    out[(program, op)].append(tf_op)
    return dict(out)


def scope_of(tf_op: str) -> str:
    """The innermost program scope among the components of a ``tf_op``
    path (its last component, the op itself, excluded)."""
    parts = tf_op.split("/")[:-1]
    best, best_end = UNSCOPED, -1
    for i in range(len(parts)):
        for scope, sp in _SCOPE_PARTS:
            end = i + len(sp)
            if end > best_end and tuple(parts[i:end]) == sp:
                best, best_end = scope, end
    return best


# --------------------------------------------------------------------------
# the reduction
# --------------------------------------------------------------------------

@dataclasses.dataclass
class ProgramTrace:
    steps: float                       # decode executions, per device
    step_op_ns: float                  # their summed op time, per device
    scope_ns: Dict[str, float]         # innermost scope -> op time, per device
    spans: List[Tuple[float, float, str]]   # serve.* spans in the window
    idle_ns: Dict[str, float]          # innermost host span -> idle ns
    ambiguous: List[str]               # op names with two tf_op paths

    def scope_ms(self, scope: str) -> Optional[float]:
        """Device ms per decode step under ``scope`` and the scopes
        nested in it; None when no op of the step is."""
        hits = [t for s, t in self.scope_ns.items()
                if s == scope or s.startswith(scope + "/")]
        if not hits or not self.steps:
            return None
        return sum(hits) / self.steps * 1e-6

    def span_ns(self, name: str) -> List[float]:
        return [e - s for s, e, n in self.spans if n == name]


def _host_spans(data) -> List[Tuple[float, float, str]]:
    spans = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(("bench.", "serve.")):
                    spans.append((ev.start_ns,
                                  ev.start_ns + ev.duration_ns, ev.name))
    return spans


def _segments(spans, w0: float, w1: float):
    """The window cut where the innermost (shortest) covering span
    changes: ``[(start, end, name or None)]`` in time order."""
    edges = sorted({w0, w1} | {t for s, e, _ in spans for t in (s, e)
                               if w0 < t < w1})
    starts = sorted(spans)
    out, active, k = [], [], 0
    for a, b in zip(edges, edges[1:]):
        while k < len(starts) and starts[k][0] <= a:
            active.append(starts[k])
            k += 1
        active = [sp for sp in active if sp[1] > a]
        best = min(active, key=lambda sp: sp[1] - sp[0], default=None)
        out.append((a, b, best[2] if best else None))
    return out


def reduce_program_trace(path: str) -> ProgramTrace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    tf_ops = read_tf_ops(path)
    host = _host_spans(data)
    windows = [s for s in host if s[2] == trace_reduce.WINDOW_SPAN]
    if not windows:
        raise ValueError(f"trace has no {trace_reduce.WINDOW_SPAN!r} span")
    w0, w1 = windows[0][0], windows[0][1]
    inner = [s for s in host if s[2] != trace_reduce.WINDOW_SPAN]
    segments = _segments(inner, w0, w1)

    scope_ns: Dict[str, float] = defaultdict(float)
    idle_ns: Dict[str, float] = defaultdict(float)
    ambiguous = set()
    steps = step_op_ns = 0.0
    devices = 0
    for plane in data.planes:
        if not trace_reduce._DEVICE_PLANE.match(plane.name):
            continue
        devices += 1
        runs, ops, busy = [], [], []
        for line in plane.lines:
            if line.name not in ("XLA Ops", "XLA Modules"):
                continue
            for ev in line.events:
                s = max(ev.start_ns, w0)
                e = min(ev.start_ns + ev.duration_ns, w1)
                if e <= s:
                    continue
                if line.name == "XLA Modules":
                    m = _MODULE_ID.match(ev.name)
                    if m and m.group(1) == DECODE_MODULE:
                        runs.append((s, e, m.group(2)))
                    continue
                busy.append((s, e))
                name = trace_reduce.op_name(ev.name)
                if trace_reduce._SUFFIX.sub("", name) not in \
                        trace_reduce._CONTAINERS:
                    ops.append((s, e, ev.name))
        ops.sort()
        runs.sort()
        steps += len(runs)
        k = 0
        for r0, r1, program in runs:
            while k < len(ops) and ops[k][0] < r0:
                k += 1
            while k < len(ops) and ops[k][0] < r1:
                s, e, full = ops[k]
                k += 1
                paths = tf_ops.get((program, full), [""])
                if len(paths) > 1:
                    ambiguous.add(full)
                    scope = AMBIGUOUS
                else:
                    scope = scope_of(paths[0])
                scope_ns[scope] += e - s
                step_op_ns += e - s
        _, merged = trace_reduce._union(busy)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        _add_idle(idle_ns, list(zip(edges[0::2], edges[1::2])), segments)
    if not devices:
        raise ValueError("trace has no TPU device plane")
    return ProgramTrace(
        steps=steps / devices, step_op_ns=step_op_ns / devices,
        scope_ns={k: v / devices for k, v in scope_ns.items()},
        spans=sorted(s for s in inner if s[2].startswith("serve.")
                     and w0 <= s[0] and s[1] <= w1),
        idle_ns={k: v / devices for k, v in idle_ns.items()},
        ambiguous=sorted(ambiguous),
    )


def _add_idle(idle_ns, gaps, segments) -> None:
    """Give each idle gap's time to the segments' spans it overlaps
    (both lists in time order)."""
    k = 0
    for a, b in gaps:
        if b <= a:
            continue
        while k < len(segments) and segments[k][1] <= a:
            k += 1
        j = k
        while j < len(segments) and segments[j][0] < b:
            s, e, name = segments[j]
            overlap = min(b, e) - max(a, s)
            if overlap > 0:
                idle_ns[name or "outside spans"] += overlap
            j += 1


@functools.lru_cache(maxsize=4)
def _cached(path: str, mtime_ns: int, size: int) -> ProgramTrace:
    return reduce_program_trace(path)


def of_run(run) -> Optional[ProgramTrace]:
    """The reduction for a metric reader: None for an untraced run or
    when ``harness.TRACE_DIR`` holds no trace."""
    return None if run.trace is None else load(harness.TRACE_DIR)


def load(trace_dir: str) -> Optional[ProgramTrace]:
    """The reduction of the newest trace under ``trace_dir`` (parsed
    once per file), or None when there is no trace."""
    try:
        path = trace_reduce.find_xplane(trace_dir)
    except FileNotFoundError:
        return None
    st = os.stat(path)
    return _cached(path, st.st_mtime_ns, st.st_size)
