"""Roofline share of the dense layers' streamed prefill kernel, in
percent: the least time the traced slice's prefill waves need at the
chip's bf16 and HBM peaks (``bench/costs/paged_prefill_dense.py``, for
each layer below ``min_prune_layer``) over the ``paged_prefill_dense``
kernel's summed device time.

The waves are the engine's ``serve.prefill.chunk`` host spans that lie
wholly inside ``bench.window``; their args give the live slots' first
positions and live query rows, space-separated. Each wave's prefill
completes inside its tick (``serve.prefill.sync``), so the kernel runs
the slice counts are those of these waves. None when the slice holds no
such span or no such kernel (a program without them)."""

from __future__ import annotations

from typing import List, Tuple

from bench import harness, trace_reduce
from bench.costs import paged_prefill_dense as cost

KERNEL = "paged_prefill_dense"
WAVE_SPAN = "serve.prefill.chunk"


def _ints(value) -> List[int]:
    return [int(x) for x in str(value).split()]


def waves(path: str) -> List[List[Tuple[int, int]]]:
    """``[(start, rows), ...]`` of each chunk wave in the window."""
    from jax.profiler import ProfileData

    spans, window = [], None
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                end = ev.start_ns + ev.duration_ns
                if ev.name == trace_reduce.WINDOW_SPAN and window is None:
                    window = (ev.start_ns, end)
                elif ev.name == WAVE_SPAN:
                    spans.append((ev.start_ns, end, dict(ev.stats)))
    if window is None:
        return []
    return [list(zip(_ints(args["starts"]), _ints(args["rows"])))
            for s, e, args in sorted(spans, key=lambda sp: sp[0])
            if window[0] <= s and e <= window[1] and "starts" in args]


def read(run):
    if run.trace is None:
        return None
    kernel_ns = run.trace.kernel_ns(KERNEL)
    if not kernel_ns:
        return None
    try:
        path = trace_reduce.find_xplane(harness.TRACE_DIR)
    except FileNotFoundError:
        return None
    slice_waves = waves(path)
    if not slice_waves:
        return None
    layers = run.cfg["energon"]["min_prune_layer"]
    least = 0.0
    for wave in slice_waves:
        flops, nbytes = cost.cost(run.cfg, wave)
        least += layers * max(flops / run.peaks["bf16_flops"],
                              nbytes / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (kernel_ns * 1e-9)
