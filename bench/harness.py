"""One run of one cell: set-up, the measured window, the comparison
with the reference, and the result line.

Everything that belongs to a configuration, a traffic mix or a
per-layer metric is found by name: ``bench/configs/<config>.json``,
``bench/traffic/<traffic>.json``, ``bench/metrics/<metric>.py`` and the
cell's limits in ``bench/limits/<cell>.json``. Adding a cell adds files
and a ``BENCHMARK.json`` entry; this module does not change.

:func:`run` does not look for a chip (``run_cell.py`` does), so tests
drive it on the CPU at a small size.
"""

from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import math
import os
import shutil
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from bench import loadgen

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_DIR = os.path.join(ROOT, ".bench_out", "trace")
TRACE_SLICE_S = 4.0
DRAIN_LIMIT_S = 90.0
_TERMINAL = ("done", "failed", "cancelled", "expired", "shed")


# --------------------------------------------------------------------------
# finding a cell's files
# --------------------------------------------------------------------------

def _json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def _reports(metric: Dict, cell: str, reported_e2e: List[str]) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves", metric["name"]) in reported_e2e


def load_cell(name: str, root: str = ROOT) -> Dict[str, Any]:
    """The cell's entry, configuration, traffic, limits and metrics."""
    bench = _json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg = _json(os.path.join(root, configs[cell["config"]]["file"]))
    bdir = os.path.join(root, "bench")
    traffic = _json(os.path.join(bdir, "traffic", cell["traffic"] + ".json"))
    limits_path = os.path.join(bdir, "limits", name + ".json")
    limits = _json(limits_path) if os.path.exists(limits_path) else {}
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    names = [m["name"] for m in e2e]
    per_layer = [m for m in bench["per_layer"] if _reports(m, name, names)]
    return {"name": name, "cell": cell, "cfg": cfg, "traffic": traffic,
            "limits": limits, "end_to_end": e2e, "per_layer": per_layer,
            "chips": cell["chips"]}


def load_reader(metric: str, root: str = ROOT) -> Callable:
    path = os.path.join(root, "bench", "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# --------------------------------------------------------------------------
# helpers
# --------------------------------------------------------------------------

def percentile(values: List[float], p: float) -> float:
    """Nearest-rank percentile; ``inf`` entries (requests that never
    answered) sort last."""
    if not values:
        return math.nan
    v = sorted(values)
    return v[max(0, math.ceil(p / 100.0 * len(v)) - 1)]


class CompileCounter:
    """Counts XLA backend compilations (a persistent-cache load counts
    too) through JAX's monitoring events."""

    def __init__(self):
        import jax.monitoring as mon

        self.count = 0
        self.seconds = 0.0
        self.hits = self.misses = 0
        mon.register_event_duration_secs_listener(self._on)
        mon.register_event_listener(self._event)

    def _on(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += secs

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def enable_compile_cache() -> None:
    """JAX's persistent cache at a fixed directory inside the checkout;
    every program is kept, however fast it compiled."""
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)


class Record:
    """One request as a client sees it."""

    def __init__(self, uid, prompt, max_new, due):
        self.uid, self.prompt, self.max_new, self.due = \
            uid, prompt, max_new, due
        self.sent = None
        self.commits: List[float] = []
        self.req = None
        self.preempted = False

    def on_token(self, req, tok):
        self.commits.append(time.perf_counter())

    @property
    def finished(self) -> bool:
        return self.req is not None and self.req.state in _TERMINAL

    @property
    def done(self) -> bool:
        return self.req is not None and self.req.state == "done"


class Sender:
    """Sends a mix's requests to the engine and keeps their stamps."""

    def __init__(self, engine, traffic: loadgen.Traffic, span):
        from bench import program

        self.engine, self.traffic, self.span = engine, traffic, span
        self._program = program
        self.records: List[Record] = []
        self.live: List[Record] = []
        self._uid = 0

    def submit(self, prompt, max_new, due) -> Record:
        rec = Record(self._uid, prompt, max_new, due)
        self._uid += 1
        rec.req = self._program.request(rec.uid, prompt, max_new,
                                        rec.on_token)
        with self.span("bench.submit"):
            rec.sent = time.perf_counter()
            self.engine.submit(rec.req)
        self.records.append(rec)
        self.live.append(rec)
        return rec

    def tick(self) -> List[Record]:
        """One engine tick; returns the requests it finished."""
        before = self.engine.metrics.preemptions
        with self.span("bench.tick"):
            self.engine.tick()
        if self.engine.metrics.preemptions != before:
            for r in self.live:
                if r.req.state == "preempted":
                    r.preempted = True
        ended = [r for r in self.live if r.finished]
        if ended:
            self.live = [r for r in self.live if not r.finished]
        return ended

    def drain(self, limit_s: float) -> bool:
        t_stop = time.perf_counter() + limit_s
        while self.live and time.perf_counter() < t_stop:
            self.tick()
        return not self.live


class RunData:
    """What a per-layer metric reader may read."""

    def __init__(self, cfg, window_s, counters, trace, decode_calls, peaks):
        self.cfg, self.window_s, self.counters = cfg, window_s, counters
        self.trace, self.decode_calls, self.peaks = trace, decode_calls, peaks


# --------------------------------------------------------------------------
# the run
# --------------------------------------------------------------------------

def run(spec: Dict[str, Any], seed: int, seconds: float, trace: bool,
        t_process: float, *, hooks: Optional[Callable] = None,
        control: bool = False, log=print) -> Dict[str, Any]:
    """Run one cell and return its result dict (the last stdout line).

    ``spec`` is :func:`load_cell`'s dict; ``t_process`` the perf_counter
    reading at process start (set-up counts from it); ``hooks(engine)``
    may replace engine parts (tests plant faults with it); ``control``
    also reads the float8 control's gaps on the same sample (calibration
    only: benchmark runs do not).
    """
    import jax

    from bench import program

    cfg, mix = spec["cfg"], spec["traffic"]
    eng = mix["engine"]
    loadgen.check_geometry(mix, cfg["energon"]["decode_key_block"])
    compiles = CompileCounter()
    span = jax.profiler.TraceAnnotation if trace else \
        (lambda name: contextlib.nullcontext())

    engine = program.build_engine(cfg, eng, seed)
    fused = program.fused_paths(engine)
    if hooks is not None:
        hooks(engine)
    traffic = loadgen.Traffic(mix, seed, cfg["vocab_size"])
    sender = Sender(engine, traffic, span)

    # set-up: the shared prefix into the prefix cache, then one short
    # request per slot through every program the window runs
    if traffic.shared:
        sender.submit(traffic.shared, 1, due=time.perf_counter())
        sender.drain(DRAIN_LIMIT_S)
    for _ in range(eng["batch_slots"]):
        sender.submit(*traffic.warmup_request(), due=time.perf_counter())
    if not sender.drain(DRAIN_LIMIT_S) or \
            not all(r.done for r in sender.records):
        raise RuntimeError("set-up requests did not finish")
    warm = len(sender.records)
    decode_calls: List = []
    recording = [False]
    if trace:
        real_step = engine.step_fn

        def recorded(params, cache, inputs, cache_index):
            if recording[0]:
                decode_calls.append((program.decode_lengths(engine) + 1,
                                     np.asarray(inputs["active"])))
            return real_step(params, cache, inputs, cache_index)

        engine.step_fn = recorded
    c_setup = compiles.count
    c0 = program.engine_counters(engine)
    t_start = time.perf_counter()
    setup_s = t_start - t_process
    t_end = t_start + seconds

    # the window (with --trace 1, a traced slice inside it)
    loop = _loop(sender, mix, t_start, seconds)
    summary = None
    if trace:
        t_a = t_start + seconds / 3
        loop.run(t_a)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        os.makedirs(TRACE_DIR)
        jax.profiler.start_trace(TRACE_DIR)
        recording[0] = True
        with jax.profiler.TraceAnnotation("bench.window"):
            loop.run(min(t_a + min(TRACE_SLICE_S, seconds / 3), t_end))
        recording[0] = False
        jax.profiler.stop_trace()
    loop.run(t_end)
    c_window = compiles.count - c_setup
    c1 = program.engine_counters(engine)
    t_close = time.perf_counter()
    drained = sender.drain(DRAIN_LIMIT_S)
    drain_s = time.perf_counter() - t_close

    device = jax.devices()[0]
    stats = device.memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    window_s = t_end - t_start
    recs = sender.records[warm:]
    due = [r for r in recs if t_start <= r.due < t_end]
    lat = [r.sent - r.due for r in due]
    ttft = [(r.commits[0] - r.due) if r.commits else math.inf for r in due]
    gaps = [b - a for r in recs for a, b in zip(r.commits, r.commits[1:])
            if t_start <= b < t_end]
    tokens = sum(1 for r in recs for t in r.commits if t_start <= t < t_end)
    unfinished = sum(1 for r in due if not r.done)
    counters = {k: c1[k] - c0[k] for k in c0}
    log(f"[window] {window_s:.3f} s: {len(due)} requests due, "
        f"{sum(r.done for r in due)} completed, {unfinished} failed or "
        f"unfinished; {tokens} tokens committed; compiles in window "
        f"{c_window} (set-up {c_setup}, {compiles.seconds:.1f} s, cache "
        f"{compiles.hits} hits {compiles.misses} misses); drain "
        f"{drain_s:.2f} s ({'complete' if drained else 'cut'})")
    log(f"[generator] late by p50 {percentile(lat, 50) * 1e3:.3f} ms, max "
        f"{max(lat, default=0) * 1e3:.3f} ms")
    log(f"[engine] {json.dumps(counters)}")
    log(f"[memory] peak_bytes_in_use {peak}; pool {cfg['pool_pages']} pages "
        f"of {cfg['energon']['decode_key_block']} rows; fused kernels "
        f"{'selected' if fused else 'NOT selected'}")

    metrics: Dict[str, Dict] = {}
    breakdown = None
    if not trace:
        values = {
            "setup_s": setup_s,
            "tok_s": tokens / window_s,
            "ttft_p90_ms": percentile(ttft, 90) * 1e3,
            "itl_p95_ms": percentile(gaps, 95) * 1e3,
            "itl_p50_ms": percentile(gaps, 50) * 1e3,
        }
        for m in spec["end_to_end"]:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    else:
        from bench import peaks as peak_table
        from bench import trace_reduce

        summary = trace_reduce.reduce_trace(
            trace_reduce.find_xplane(TRACE_DIR))
        breakdown = trace_reduce.breakdown(summary)
        data = RunData(cfg, window_s, counters, summary, decode_calls,
                       peak_table.peaks(device.device_kind))
        for m in spec["per_layer"]:
            v = load_reader(m["name"])(data)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        log(f"[trace] slice {summary.window_ns * 1e-9:.3f} s, busy "
            f"{summary.busy_ns * 1e-9:.3f} s, {len(decode_calls)} decode "
            f"steps recorded, decode module runs "
            f"{len(summary.module_times(program.DECODE_MODULE))}, filter "
            f"calls {summary.kernel_calls(program.DECODE_KERNELS[0])}")

    # the comparison with the reference, after the program is freed
    # a preempted request re-prefills what it had decoded, so its later
    # tokens follow another computation than one uninterrupted pass:
    # the sample is drawn from the requests that were never preempted
    rng = traffic.sample_rng
    finished = [r for r in due if r.done and not r.preempted]
    sample = []
    if finished:
        longest = max(finished, key=lambda r: (len(r.req.tokens_out), -r.uid))
        rest = [r for r in finished if r is not longest]
        k = min(mix["sample"] - 1, len(rest))
        pick = rng.choice(len(rest), size=k, replace=False) if k else []
        sample = [longest] + [rest[i] for i in sorted(pick)]
    served = [list(r.req.tokens_out) for r in sample]
    prompts = [list(r.prompt) for r in sample]
    n_preempted = sum(r.preempted for r in recs)
    del engine, sender, loop, recs, due, finished, sample
    gc.collect()
    gap = widest = math.inf
    per_prog = per_ctrl = None
    t_ref = time.perf_counter()
    if served:
        per_prog, per_ctrl = token_gaps(cfg, seed, traffic.shared, prompts,
                                        served, mix, control)
        gap = gap_mean(per_prog)
        widest = max(max(g) for g in per_prog)
    log(f"[reference] {compiles.count} compiles in all; "
        f"{n_preempted} preempted requests not "
        f"sampled; {len(served)} requests, {sum(map(len, served))} "
        f"served tokens compared in {time.perf_counter() - t_ref:.1f} s; "
        f"widest gap {widest!r}")
    limit = spec["limits"].get("logit_gap_mean")
    compared = {
        "logit_gap_mean": {"value": gap, "limit": limit},
        "unfinished": {"value": unfinished, "limit": 0},
    }
    if control and per_ctrl is not None:
        compared["control_gap_mean"] = {"value": gap_mean(per_ctrl),
                                        "limit": limit}
    correct = (limit is not None and gap <= limit and unfinished == 0)
    out = {
        "correct": bool(correct),
        "attempted": len(lat),
        "failed": unfinished,
        "metrics": metrics,
        "device": {"platform": device.platform, "kind": device.device_kind,
                   "count": len(jax.devices()), "memory_peak_bytes": peak},
    }
    if summary is not None:
        out["device"]["busy_s"] = summary.busy_ns * 1e-9
        out["device"]["window_s"] = summary.window_ns * 1e-9
        out["breakdown"] = breakdown
    if control:
        out["gaps"] = {"program": per_prog, "control": per_ctrl}
    out["compared"] = compared
    return out


class _ClosedLoop:
    """Each client sends its next request when its last one ends."""

    def __init__(self, sender, clients):
        self.sender = sender
        self.waiting = [time.perf_counter()] * clients   # next due
        self.busy: List[Optional[Record]] = [None] * clients

    def run(self, t_end):
        d = self.sender
        while time.perf_counter() < t_end:
            now = time.perf_counter()
            with d.span("bench.generator"):
                for c, rec in enumerate(self.busy):
                    if rec is None and self.waiting[c] <= now:
                        self.busy[c] = d.submit(*d.traffic.next_request(),
                                                due=self.waiting[c])
            for rec in d.tick():
                c = self.busy.index(rec)
                self.busy[c] = None
                self.waiting[c] = rec.commits[-1] if rec.commits else \
                    time.perf_counter()


class _OpenLoop:
    """Requests are due on the mix's schedule, whatever the engine does."""

    def __init__(self, sender, due):
        self.sender, self.due, self.next = sender, due, 0

    def run(self, t_end):
        d, due = self.sender, self.due
        while True:
            now = time.perf_counter()
            if now >= t_end:
                return
            with d.span("bench.generator"):
                while self.next < len(due) and due[self.next] <= now:
                    d.submit(*d.traffic.next_request(), due=due[self.next])
                    self.next += 1
            if d.live:
                d.tick()
            else:
                nxt = due[self.next] if self.next < len(due) else t_end
                with d.span("bench.idle"):
                    time.sleep(max(0.0, min(nxt, t_end) - now))


def _loop(sender, mix, t_start, seconds):
    if mix["loop"] == "closed":
        return _ClosedLoop(sender, mix["clients"])
    return _OpenLoop(sender, [t_start + a for a in
                              sender.traffic.arrivals(seconds)])


def _reference(cfg, seed, shared, prompts, served, mix, evals, low=False):
    from bench.reference import forward

    eng = mix["engine"]
    S = len(shared)
    reqs = [(p[S:] + t[:-1], len(p)) for p, t in zip(prompts, served)]
    # a fixed number of requests keeps the reference's shapes, and so
    # its compiled programs, the same in every run; padding repeats the
    # first request and its rows are not read
    pad = mix["sample"] - len(reqs)
    reqs += [reqs[0]] * pad
    evals = np.concatenate([evals] + [evals[:1]] * pad)
    return forward.reference_logits(
        cfg, seed, shared=shared, requests=reqs, eval_tokens=evals,
        max_len=eng["max_len"], chunk=eng["prefill_chunk"],
        served=mix["output"]["max"], low=low)


def _evals(served, n, extra=()):
    evals = np.zeros((len(served), n, 1 + len(extra)), np.int32)
    for r, toks in enumerate(served):
        evals[r, :len(toks), 0] = toks
        for m, tab in enumerate(extra):
            evals[r, :len(toks), 1 + m] = tab[r, :len(toks)]
    return evals


def token_gaps(cfg, seed, shared, prompts, served, mix, control=False):
    """Per served token, the gap by which its reference logit lies below
    the reference's best, as lists per request; with ``control`` also
    the float8 control's gaps on the same prompts and tokens (the
    reference in float8, ``low=True``, read at each position by the gap
    of the token it puts first), else None."""
    n = mix["output"]["max"]
    extra = ()
    if control:
        _, low_first, _ = _reference(cfg, seed, shared, prompts, served,
                                     mix, _evals(served, n), low=True)
        extra = (low_first,)
    best, _, picked = _reference(cfg, seed, shared, prompts, served, mix,
                                 _evals(served, n, extra))
    gaps = best[..., None] - picked

    def rows(m):
        return [gaps[r, :len(t), m].tolist() for r, t in enumerate(served)]

    return rows(0), (rows(1) if control else None)


def gap_mean(per_request) -> float:
    """Mean of the per-token gaps of a sample."""
    flat = [g for req in per_request for g in req]
    return sum(flat) / len(flat)
