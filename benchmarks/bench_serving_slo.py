"""Serving SLO goodput bench (BENCH_serving_slo.json).

A Poisson multi-tenant load generator drives the serving engine through
the head-of-line-stall scenario the hybrid scheduler exists for: a deep
queue (thousands of requests at the default size) of short interactive
prompts punctured by long-context admissions, three shared system
prompts stressing the prefix trie, tenants and priority classes in the
mix. The same trace runs under both schedulers:

* ``sync`` — the pre-hybrid tick: admission runs a prompt's *entire*
  chunked prefill wave before the decode step dispatches, so every live
  stream's inter-token gap absorbs the whole wave;
* ``hybrid`` — each tick interleaves at most one prefill chunk wave
  with the decode step, so the same admission costs live streams a few
  chunk-sized stalls.

Per-uid token streams must be bit-identical between the two runs (the
scheduler equivalence contract — checked here end to end), which also
pins total tokens equal, so the latency comparison happens at equal
work. Reported per scheduler: wall inter-token latency (p50/p95),
decode-attributed ITL (tick-phase attribution strips scheduler stalls —
the truthful "how fast is decode" histogram), TTFT from submit and from
admission, throughput, and **goodput at a stated TTFT/ITL SLO**: tokens
per second from requests that were served within the SLO
(admission-to-first-token ≤ ``--slo-ttft-ms`` AND per-request p95 wall
ITL ≤ ``--slo-itl-ms``).

The headline gate: pooled wall ITL p95 under concurrent long-prompt
admission improves ≥ 2x over the synchronous tick at equal total
tokens.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time


def _pct(vals, p):
    import numpy as np
    return float(np.percentile(np.asarray(vals), p)) if vals else 0.0


def build_trace(cfg, *, requests, seed, tenants, long_every,
                system_tokens, new_tokens, arrival_rate):
    """Poisson arrivals (exponential gaps at ``arrival_rate`` req/s) of
    multi-tenant requests over three shared system prompts. Every
    ``long_every``-th request carries a long context (8-10 prefill
    chunks at chunk 32) — the head-of-line stressor; the rest are short
    interactive prompts. ~10% ride a higher priority class."""
    import numpy as np

    rng = np.random.default_rng(seed)
    systems = [
        [int(t) for t in rng.integers(1, cfg.vocab_size - 1,
                                      size=system_tokens)]
        for _ in range(3)
    ]
    gaps = rng.exponential(1.0 / arrival_rate, size=requests)
    arrivals = np.cumsum(gaps)
    trace = []
    for uid in range(requests):
        long = long_every > 0 and uid % long_every == 0
        body_len = (int(rng.integers(192, 289)) if long
                    else int(rng.integers(8, 49)))
        body = [int(t) for t in rng.integers(1, cfg.vocab_size - 1,
                                             size=body_len)]
        trace.append(dict(
            uid=uid,
            prompt=list(systems[uid % len(systems)]) + body,
            max_new_tokens=new_tokens,
            temperature=0.7 if uid % 2 else 0.0,
            tenant=f"tenant{uid % tenants}",
            priority=1 if uid % 10 == 9 else 0,
        ))
    return trace, [float(t) for t in arrivals]


def run_scheduler(scheduler, model, cfg, params, trace, arrivals, *,
                  batch_slots, num_pages, prefill_chunk, max_len,
                  admission_lookahead, slo_ttft_ms, slo_itl_ms):
    """Drain the trace under one scheduler with Poisson-paced
    submissions; returns (per-uid streams, metrics record)."""
    import jax

    from repro.runtime import Request, ServeLoop

    engine = ServeLoop(
        model, params, batch_slots=batch_slots, max_len=max_len,
        prefill_chunk=prefill_chunk, num_pages=num_pages,
        eos_token=cfg.vocab_size - 1, scheduler=scheduler,
        admission_lookahead=admission_lookahead,
        rng=jax.random.PRNGKey(0),
    )
    reqs = [Request(**r) for r in trace]
    first_tick_at = {}

    peak_queue = 0
    t0 = time.perf_counter()
    nxt = 0
    while nxt < len(reqs) or engine._has_work():
        now = time.perf_counter() - t0
        while nxt < len(reqs) and arrivals[nxt] <= now:
            engine.submit(reqs[nxt])
            nxt += 1
        peak_queue = max(peak_queue, len(engine.pending))
        if engine._has_work():
            engine.tick()
        # between arrivals with nothing in flight: jump to the next
        # arrival instead of spinning
        elif nxt < len(reqs):
            time.sleep(max(arrivals[nxt] - (time.perf_counter() - t0), 0))
    wall = time.perf_counter() - t0
    done = engine.completed
    assert len(done) == len(trace), (scheduler, len(done))

    itl_all, itl_decode_all, itl_stalled = [], [], []
    ttft_submit, ttft_admit = [], []
    slo_met_tokens = 0
    slo_met_requests = 0
    for r in done:
        gaps = list(r._itl)
        dec = list(r._itl_decode)
        itl_all += gaps
        itl_decode_all += dec
        # gaps punctured by a concurrent prefill phase (tick-phase
        # attribution found scheduler stall inside the gap)
        itl_stalled += [g for g, d in zip(gaps, dec) if g - d > 1e-7]
        ttft_submit.append(r._t_first - r._t_submit)
        ttft_admit.append(r._t_first - r._t_admit)
        ok = (
            (r._t_first - r._t_admit) * 1e3 <= slo_ttft_ms
            and (_pct(gaps, 95.0) * 1e3 <= slo_itl_ms if gaps else True)
        )
        if ok:
            slo_met_requests += 1
            slo_met_tokens += len(r.tokens_out)

    m = engine.metrics
    total_tokens = sum(len(r.tokens_out) for r in done)
    streams = {r.uid: tuple(r.tokens_out) for r in done}
    record = {
        "scheduler": scheduler,
        "wall_seconds": wall,
        "completed": len(done),
        "total_tokens": total_tokens,
        "throughput_tok_s": total_tokens / max(wall, 1e-9),
        "peak_queue_depth": peak_queue,
        "ticks": m.ticks,
        "prefill_dispatches": m.prefill_dispatches,
        "decode_dispatches": m.decode_dispatches,
        "preemptions": m.preemptions,
        "prefix_hit_rate": m.prefix_hit_rate,
        "prefill_tokens_skipped": m.prefill_tokens_skipped,
        "itl_p50_ms": _pct(itl_all, 50.0) * 1e3,
        "itl_p95_ms": _pct(itl_all, 95.0) * 1e3,
        "itl_decode_p50_ms": _pct(itl_decode_all, 50.0) * 1e3,
        "itl_decode_p95_ms": _pct(itl_decode_all, 95.0) * 1e3,
        "stalled_gaps": len(itl_stalled),
        "itl_stalled_p95_ms": _pct(itl_stalled, 95.0) * 1e3,
        "ttft_submit_p95_ms": _pct(ttft_submit, 95.0) * 1e3,
        "ttft_admit_p50_ms": _pct(ttft_admit, 50.0) * 1e3,
        "ttft_admit_p95_ms": _pct(ttft_admit, 95.0) * 1e3,
        "slo_met_requests": slo_met_requests,
        "goodput_tok_s": slo_met_tokens / max(wall, 1e-9),
    }
    return streams, record


def run_serving_slo_bench(*, requests=2000, seed=0, tenants=6,
                          long_every=6, new_tokens=8, batch_slots=4,
                          num_pages=24, prefill_chunk=32,
                          arrival_rate=400.0, admission_lookahead=4,
                          slo_ttft_ms=2000.0, slo_itl_ms=100.0):
    import jax

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from bench_throughput import _serve_model

    from repro.kernels.ops import _default_interpret

    cfg, model, params = _serve_model()
    # system prompts span two full pages (page_size 64) so the prefix
    # trie actually registers and shares them
    system_tokens = 128
    max_len = 448  # 128 system + ≤288 body + generation, 7 pages
    trace, arrivals = build_trace(
        cfg, requests=requests, seed=seed, tenants=tenants,
        long_every=long_every, system_tokens=system_tokens,
        new_tokens=new_tokens, arrival_rate=arrival_rate,
    )
    kw = dict(
        batch_slots=batch_slots, num_pages=num_pages,
        prefill_chunk=prefill_chunk, max_len=max_len,
        admission_lookahead=admission_lookahead,
        slo_ttft_ms=slo_ttft_ms, slo_itl_ms=slo_itl_ms,
    )
    results = {}
    streams = {}
    for scheduler in ("sync", "hybrid"):
        streams[scheduler], results[scheduler] = run_scheduler(
            scheduler, model, cfg, params, trace, arrivals, **kw
        )
        r = results[scheduler]
        print(f"[slo] {scheduler}: {r['completed']} req, "
              f"{r['total_tokens']} tok in {r['wall_seconds']:.1f}s "
              f"({r['throughput_tok_s']:.0f} tok/s), peak queue "
              f"{r['peak_queue_depth']}, itl p95 {r['itl_p95_ms']:.1f} ms "
              f"(decode-attributed {r['itl_decode_p95_ms']:.1f} ms), "
              f"goodput {r['goodput_tok_s']:.0f} tok/s "
              f"({r['slo_met_requests']} in SLO)")

    identical = streams["hybrid"] == streams["sync"]
    h, s = results["hybrid"], results["sync"]
    record = {
        "schema": 1,
        "host_backend": jax.default_backend(),
        "kernel_mode": "interpret" if _default_interpret() else "compiled",
        "slo": {"ttft_admit_ms": slo_ttft_ms, "itl_p95_ms": slo_itl_ms},
        "trace": {
            "requests": requests,
            "seed": seed,
            "tenants": tenants,
            "long_every": long_every,
            "system_prompt_tokens": system_tokens,
            "new_tokens": new_tokens,
            "arrival_rate_req_s": arrival_rate,
            "batch_slots": batch_slots,
            "num_pages": num_pages,
            "prefill_chunk": prefill_chunk,
        },
        "sync": s,
        "hybrid": h,
        "streams_identical": identical,
        "itl_p95_improvement": s["itl_p95_ms"] / max(h["itl_p95_ms"],
                                                     1e-9),
        "itl_stalled_p95_improvement": (
            s["itl_stalled_p95_ms"] / max(h["itl_stalled_p95_ms"], 1e-9)
        ),
        "goodput_improvement": (
            h["goodput_tok_s"] / max(s["goodput_tok_s"], 1e-9)
        ),
        "equal_total_tokens": h["total_tokens"] == s["total_tokens"],
    }
    print(f"[slo] streams identical: {identical}; itl p95 improvement "
          f"{record['itl_p95_improvement']:.2f}x (stalled gaps "
          f"{record['itl_stalled_p95_improvement']:.2f}x), goodput "
          f"{record['goodput_improvement']:.2f}x")
    return record


def write_serving_slo_json(path, record):
    with open(path, "w") as f:
        json.dump(record, f, indent=2)
    print(f"[slo] wrote {path}")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default="BENCH_serving_slo.json")
    ap.add_argument("--requests", type=int, default=2000,
                    help="trace size (default queues thousands — the "
                         "backlog regime the pending-queue fix targets)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tenants", type=int, default=6)
    ap.add_argument("--long-every", type=int, default=6,
                    help="every k-th request carries a 192-288 token "
                         "context (the head-of-line stressor)")
    ap.add_argument("--new-tokens", type=int, default=8)
    ap.add_argument("--batch-slots", type=int, default=4)
    ap.add_argument("--num-pages", type=int, default=24,
                    help="pool size (28 = no oversubscription at 4 "
                         "slots; 24 exercises preemption)")
    ap.add_argument("--prefill-chunk", type=int, default=32)
    ap.add_argument("--arrival-rate", type=float, default=400.0,
                    help="Poisson arrival rate, requests/second")
    ap.add_argument("--admission-lookahead", type=int, default=4)
    ap.add_argument("--slo-ttft-ms", type=float, default=2000.0,
                    help="SLO: admission-to-first-token budget")
    ap.add_argument("--slo-itl-ms", type=float, default=100.0,
                    help="SLO: per-request p95 inter-token budget")
    args = ap.parse_args(argv)

    record = run_serving_slo_bench(
        requests=args.requests, seed=args.seed, tenants=args.tenants,
        long_every=args.long_every, new_tokens=args.new_tokens,
        batch_slots=args.batch_slots, num_pages=args.num_pages,
        prefill_chunk=args.prefill_chunk,
        arrival_rate=args.arrival_rate,
        admission_lookahead=args.admission_lookahead,
        slo_ttft_ms=args.slo_ttft_ms, slo_itl_ms=args.slo_itl_ms,
    )
    write_serving_slo_json(args.json, record)


if __name__ == "__main__":
    main()
