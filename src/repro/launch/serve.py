"""Serving launcher: chunked-prefill + batched sparse decode over a
paged KV cache.

``python -m repro.launch.serve --arch <id> --smoke`` starts the
continuous-batching engine on synthetic requests and reports prefill and
decode throughput plus per-request latency percentiles. The cache is
paged whenever the arch supports it (``--unpaged`` forces the
contiguous layout; ``--num-pages`` oversubscribes the pool below
``slots × blocks`` to exercise preemption). The full-size serve_step is
exercised by the decode_* dry-run shapes.
"""

from __future__ import annotations

import argparse
import os
import re
import time


def _force_host_devices(n: int) -> None:
    """Set ``--xla_force_host_platform_device_count=n`` in XLA_FLAGS,
    replacing any existing value. Must run before the first jax import
    (the backend reads the flag once at initialization)."""
    flags = os.environ.get("XLA_FLAGS", "")
    flags = re.sub(
        r"--xla_force_host_platform_device_count=\d+\s*", "", flags
    ).strip()
    os.environ["XLA_FLAGS"] = (
        f"{flags} --xla_force_host_platform_device_count={n}".strip()
    )


def _parse_mesh(spec: str):
    m = re.fullmatch(r"(\d+)x(\d+)", spec.strip().lower())
    if m is None:
        raise SystemExit(f"--mesh wants DxM (e.g. 2x2), got {spec!r}")
    return int(m.group(1)), int(m.group(2))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--batch-slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--prefill-chunk", type=int, default=64)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--scheduler", choices=("hybrid", "sync"),
                    default="hybrid",
                    help="hybrid (default): each tick interleaves one "
                         "prefill chunk wave with the decode step, so "
                         "long admissions never freeze live streams; "
                         "sync: the pre-hybrid whole-wave-per-admission "
                         "schedule (same per-uid streams, bit for bit)")
    ap.add_argument("--admission-lookahead", type=int, default=0,
                    metavar="K",
                    help="let up to K queued requests behind a head too "
                         "big for the free pool admit ahead of it "
                         "(0 = strict policy order)")
    ap.add_argument("--unpaged", action="store_true",
                    help="force the contiguous batch×max_len cache")
    ap.add_argument("--num-pages", type=int, default=None,
                    help="paged pool size (default slots×blocks; smaller "
                         "values oversubscribe and may preempt)")
    ap.add_argument("--no-prefix-sharing", action="store_true",
                    help="disable the shared-prefix page cache "
                         "(copy-on-write prefix reuse across requests)")
    ap.add_argument("--system-prompt-len", type=int, default=0,
                    help="prepend this many shared system-prompt tokens "
                         "to every request (exercises prefix sharing)")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-request TTL in milliseconds: the engine "
                         "evicts an expired request at any state "
                         "(queued, live, preempted-requeued)")
    ap.add_argument("--queue-limit", type=int, default=None,
                    help="bounded admission queue: submissions past "
                         "this depth are rejected (QueueFull) and "
                         "counted as shed")
    ap.add_argument("--chaos-seed", type=int, default=None,
                    help="run under a seeded FaultInjector (allocation "
                         "denials, step exceptions, NaN logits, "
                         "preemption storms) — the same seed replays "
                         "the same fault schedule")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a Chrome/Perfetto trace of the run here "
                         "(open in ui.perfetto.dev); implies "
                         "observability")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write the metrics/sparsity JSON snapshot here; "
                         "implies observability")
    ap.add_argument("--obs", action="store_true",
                    help="attach the observability layer (event trace, "
                         "sparsity telemetry, metrics registry) even "
                         "without an export path")
    ap.add_argument("--mesh", default=None, metavar="DxM",
                    help="serve over a (data, model) device mesh, e.g. "
                         "2x2: D engine replicas behind one shared "
                         "admission queue, each tensor-parallel over M "
                         "devices (head-sharded page pools + shard_map "
                         "fused kernels)")
    ap.add_argument("--simulate-devices", type=int, default=None,
                    metavar="N",
                    help="fake N host devices via XLA_FLAGS "
                         "--xla_force_host_platform_device_count (must "
                         "be set before jax imports — this flag handles "
                         "that); lets --mesh run on a laptop CPU")
    args = ap.parse_args()

    if args.simulate_devices is not None:
        _force_host_devices(args.simulate_devices)

    import jax
    import numpy as np

    from repro.configs.registry import get_config, get_smoke_config
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import LMModel
    from repro.runtime import (
        FaultInjector, FaultSpec, QueueFull, ReplicatedServeLoop, Request,
        ServeLoop, attention_cache_bytes,
    )

    enable_compile_cache()
    mesh = None
    mesh_shape = None
    if args.mesh is not None:
        from repro.launch.mesh import make_mesh

        mesh_shape = _parse_mesh(args.mesh)
        need = mesh_shape[0] * mesh_shape[1]
        have = len(jax.devices())
        if have < need:
            raise SystemExit(
                f"--mesh {args.mesh} needs {need} devices, have {have} "
                f"(try --simulate-devices {need})"
            )
        mesh = make_mesh(mesh_shape, ("data", "model"))

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    model = LMModel(cfg)
    params = model.init(jax.random.PRNGKey(0))

    obs = None
    if args.obs or args.trace_out or args.metrics_out:
        from repro.observability import Observability
        obs = Observability()

    injector = None
    if args.chaos_seed is not None:
        injector = FaultInjector(
            seed=args.chaos_seed,
            spec=FaultSpec(
                alloc_failure=0.05, step_exception=0.05,
                nan_logits=0.01, nan_prefill=0.01,
                preempt_storm=0.05,
            ),
        )
    paged = None if not args.unpaged else False
    engine_kw = dict(
        batch_slots=args.batch_slots, max_len=args.max_len,
        eos_token=cfg.vocab_size - 1, prefill_chunk=args.prefill_chunk,
        scheduler=args.scheduler,
        admission_lookahead=args.admission_lookahead,
        paged=paged, num_pages=args.num_pages,
        prefix_sharing=(False if (args.no_prefix_sharing or args.unpaged)
                        else None),
        queue_limit=args.queue_limit,
        default_deadline_s=(
            args.deadline_ms / 1e3 if args.deadline_ms is not None else None
        ),
        fault_injector=injector,
        observability=obs,
    )
    replicated = mesh is not None and mesh_shape[0] > 1
    if replicated:
        engine = ReplicatedServeLoop(model, params, mesh=mesh, **engine_kw)
    elif mesh is not None:
        engine = ServeLoop(model, params, mesh=mesh, **engine_kw)
    else:
        engine = ServeLoop(model, params, **engine_kw)
    if mesh is not None:
        print(f"[serve] mesh {mesh_shape[0]}x{mesh_shape[1]} "
              f"(data x model) over {len(jax.devices())} "
              f"{jax.devices()[0].platform} devices"
              + (f", {mesh_shape[0]} engine replicas" if replicated
                 else ""))
    rng = np.random.default_rng(0)
    system = rng.integers(
        1, cfg.vocab_size - 1, size=args.system_prompt_len
    ).tolist()
    rejected = 0
    for uid in range(args.requests):
        prompt = system + rng.integers(
            1, cfg.vocab_size - 1, size=args.prompt_len
        ).tolist()
        try:
            engine.submit(Request(uid=uid, prompt=prompt,
                                  max_new_tokens=args.new_tokens))
        except QueueFull:
            rejected += 1
    t0 = time.perf_counter()
    done = engine.run_until_drained()
    dt = time.perf_counter() - t0
    eng0 = engine.engines[0] if replicated else engine
    m = engine.merged_metrics() if replicated else engine.metrics
    total_tokens = sum(len(r.tokens_out) for r in done)
    mode = "chunked" if eng0.prefill_fn is not None else "sequential"
    cache_mode = "paged" if eng0.paged else "contiguous"
    print(f"[serve] {cfg.name}: {len(done)} requests, {total_tokens} tokens "
          f"in {dt:.2f}s ({total_tokens/dt:.1f} tok/s end-to-end)")
    print(f"[serve] prefill ({mode}): {m.prefill_tokens} tok in "
          f"{m.prefill_dispatches} dispatches "
          f"({m.prefill_tokens_per_sec:.1f} tok/s)")
    print(f"[serve] decode: {m.decode_tokens} tok in "
          f"{m.decode_dispatches} dispatches "
          f"({m.decode_tokens_per_sec:.1f} tok/s, {m.ticks} ticks)")
    lat = m.latency_stats()
    print(f"[serve] latency: ttft p50/p95 "
          f"{lat['ttft_p50']*1e3:.1f}/{lat['ttft_p95']*1e3:.1f} ms, "
          f"itl p50/p95 {lat['itl_p50']*1e3:.1f}/{lat['itl_p95']*1e3:.1f} ms "
          f"(decode-attributed p95 {lat['itl_decode_p95']*1e3:.1f} ms), "
          f"queue p95 {lat['queue_wait_p95']*1e3:.1f} ms")
    if eng0.paged:
        pool = attention_cache_bytes(eng0.cache)
        page = pool // eng0.layout.num_pages
        per_rep = " per replica" if replicated else ""
        print(f"[serve] cache ({cache_mode}): "
              f"{eng0.layout.num_pages} pages × {page} B = "
              f"{pool} B pool{per_rep}, "
              f"peak {m.peak_pages_in_use} pages in use "
              f"({m.peak_pages_in_use * page} B), "
              f"{m.preemptions} preemptions")
        if eng0.sharing:
            print(f"[serve] prefix cache: hit-rate "
                  f"{m.prefix_hit_rate:.2f} "
                  f"({m.prefix_hits}/{m.prefix_lookups} admissions), "
                  f"{m.pages_shared} pages shared, "
                  f"{m.prefill_tokens_skipped} prefill tok skipped, "
                  f"{m.cow_clones} CoW clones")
    else:
        print(f"[serve] cache ({cache_mode}): "
              f"{attention_cache_bytes(eng0.cache)} B "
              f"({args.batch_slots} slots × {eng0.max_len} rows)")
    if replicated:
        counts = [0] * engine.n_replicas
        for r in engine.placement.values():
            counts[r] += 1
        per = " | ".join(
            f"r{e.replica_id}: {counts[e.replica_id]} req, "
            f"{e.metrics.decode_tokens} tok, {e.metrics.ticks} ticks"
            for e in engine.engines
        )
        print(f"[serve] replicas: {per}")
    evicted = engine.terminated
    if evicted or rejected or m.retries or injector is not None:
        print(f"[serve] lifecycle: {len(done)} completed, "
              f"{m.failed_requests} failed, {m.cancelled_requests} "
              f"cancelled, {m.expired_requests} expired, "
              f"{m.shed_requests + rejected} shed/rejected, "
              f"{m.retries} step retries")
    if injector is not None:
        print(f"[serve] chaos (seed {args.chaos_seed}): "
              f"{injector.total_injected} faults injected "
              f"{dict(injector.counts)}")
    if obs is not None:
        sp = obs.sparsity.snapshot()
        rho_p = sp["prefill"]["rho_eff"]
        rho_d = sp["decode"]["rho_eff"]
        pool = obs.series_stats("pool_occupancy")
        print(f"[serve] sparsity: rho_eff prefill "
              f"{'n/a' if rho_p is None else f'{rho_p:.3f}'} / decode "
              f"{'n/a' if rho_d is None else f'{rho_d:.3f}'}"
              + (f" (pinned {sp['decode']['pinned_fraction']:.2f}, "
                 f"fill {sp['decode']['fill_fraction']:.2f})"
                 if rho_d is not None else "")
              + f", pool occupancy p50/peak "
                f"{pool['p50']:.0f}/{pool['peak']:.0f} pages, "
                f"{len(obs.trace)} trace events")
        if args.trace_out:
            obs.export_chrome_trace(args.trace_out)
            print(f"[serve] chrome trace -> {args.trace_out} "
                  f"(open in ui.perfetto.dev)")
        if args.metrics_out:
            import json
            with open(args.metrics_out, "w") as f:
                json.dump(obs.snapshot(), f, indent=2)
            print(f"[serve] metrics snapshot -> {args.metrics_out}")


if __name__ == "__main__":
    main()
