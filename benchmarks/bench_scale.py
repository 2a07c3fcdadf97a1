"""Multi-device serving scaling sweep (BENCH_scale.json).

Drains the heavy mixed-length trace (4x the BENCH_serving trace — 48
requests, 8-512 token prompts) through ``ReplicatedServeLoop`` at 1, 2
and 4 engine replicas on an ``(N, 1)`` mesh of simulated host devices
(``--xla_force_host_platform_device_count``), one replica per device.

**The scaling metric is tick-normalized.** On a single host CPU the
replicas' dispatches serialize, so wall-clock measures host contention,
not the replica parallelism a real multi-device deployment gets. Each
replica's tick count is what it would execute *concurrently* on its own
device, so the parallel makespan is ``max_r ticks_r`` and

    throughput(N)        = total decode tokens / max_r ticks_r
    scaling_efficiency(N) = ticks(1) / (N * max_r ticks_r(N))

Efficiency < 1 comes from real scheduler effects the bench is meant to
surface — placement imbalance (the uid hash plus least-loaded spill),
wave quantization (ceil(requests / slots) admission waves per replica)
— not from host noise. Wall-clock decode tok/s (serial and the
max-over-replica parallel model) ride along for reference.

The sweep also re-checks the replica contract end-to-end: every
request's token stream at every N must be bit-identical to the N=1 run
(``streams_identical_across_scales``) — placement must never leak into
outputs.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys


def _force_host_devices(n: int) -> None:
    flags = os.environ.get("XLA_FLAGS", "")
    flags = re.sub(
        r"--xla_force_host_platform_device_count=\d+\s*", "", flags
    ).strip()
    os.environ["XLA_FLAGS"] = (
        f"{flags} --xla_force_host_platform_device_count={n}".strip()
    )


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default="BENCH_scale.json")
    ap.add_argument("--simulate-devices", type=int, default=4,
                    help="fake this many host devices (set before jax "
                         "imports); the sweep runs every replica count "
                         "in --sweep that fits")
    ap.add_argument("--sweep", default="1,2,4",
                    help="comma-separated replica counts")
    ap.add_argument("--trace-repeats", type=int, default=4,
                    help="heavy trace = BENCH_serving trace x this "
                         "(4 => 48 requests)")
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--batch-slots", type=int, default=4)
    ap.add_argument("--num-pages", type=int, default=16,
                    help="page-pool size PER REPLICA (16 oversubscribes "
                         "4 slots x 9 blocks and exercises preemption)")
    args = ap.parse_args(argv)

    _force_host_devices(args.simulate_devices)

    import jax
    import numpy as np

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from bench_throughput import SERVING_TRACE, _serve_model

    from repro.kernels.ops import _default_interpret
    from repro.launch.mesh import make_mesh
    from repro.runtime import ReplicatedServeLoop, Request

    sweep = sorted({int(x) for x in args.sweep.split(",")})
    sweep = [n for n in sweep if n <= len(jax.devices())]
    lengths = list(SERVING_TRACE) * args.trace_repeats
    cfg, model, params = _serve_model()

    record = {
        "schema": 1,
        "host_backend": jax.default_backend(),
        "kernel_mode": (
            "interpret" if _default_interpret() else "compiled"
        ),
        "simulated_devices": len(jax.devices()),
        "trace": {
            "prompt_lengths": lengths,
            "requests": len(lengths),
            "new_tokens": args.new_tokens,
            "batch_slots": args.batch_slots,
            "num_pages_per_replica": args.num_pages,
        },
        "replicas": {},
    }

    prompt_rng = np.random.default_rng(0)
    prompts = [
        prompt_rng.integers(1, cfg.vocab_size - 1, size=int(L)).tolist()
        for L in lengths
    ]

    streams_by_n = {}
    for n in sweep:
        mesh = make_mesh((n, 1), ("data", "model"))
        loop = ReplicatedServeLoop(
            model, params, mesh=mesh,
            batch_slots=args.batch_slots,
            max_len=528, prefill_chunk=64,
            num_pages=args.num_pages,
            rng=jax.random.PRNGKey(0),
        )
        for uid, prompt in enumerate(prompts):
            loop.submit(Request(
                uid=uid, prompt=list(prompt),
                max_new_tokens=args.new_tokens,
            ))
        import time
        t0 = time.perf_counter()
        done = loop.run_until_drained(max_ticks=100_000)
        wall = time.perf_counter() - t0
        assert len(done) == len(lengths), (n, len(done))

        streams_by_n[n] = {r.uid: tuple(r.tokens_out) for r in done}
        m = loop.merged_metrics()
        per_ticks = [e.metrics.ticks for e in loop.engines]
        max_ticks = max(per_ticks)
        counts = [0] * n
        for r in loop.placement.values():
            counts[r] += 1
        record["replicas"][str(n)] = {
            "decode_tokens": m.decode_tokens,
            "ticks_per_replica": per_ticks,
            "max_ticks": max_ticks,
            "decode_tok_per_tick": m.decode_tokens / max(max_ticks, 1),
            "wall_seconds": wall,
            "decode_tok_s_serial_wall": (
                m.decode_tokens
                / max(sum(e.metrics.decode_time
                          for e in loop.engines), 1e-9)
            ),
            "decode_tok_s_parallel_model": (
                m.decode_tokens
                / max(max(e.metrics.decode_time
                          for e in loop.engines), 1e-9)
            ),
            "goodput_tokens": sum(
                len(r.tokens_out) for r in done
            ),
            "completed": len(done),
            "preemptions": m.preemptions,
            "peak_pages_per_replica": [
                e.metrics.peak_pages_in_use for e in loop.engines
            ],
            "placement_counts": counts,
        }
        print(f"[scale] {n} replica(s): {m.decode_tokens} decode tok, "
              f"ticks/replica {per_ticks}, "
              f"{m.decode_tokens / max(max_ticks, 1):.2f} tok/tick, "
              f"placement {counts}, {m.preemptions} preemptions")

    base_ticks = record["replicas"][str(sweep[0])]["max_ticks"]
    record["scaling_efficiency"] = {
        str(n): base_ticks / (n * record["replicas"][str(n)]["max_ticks"])
        for n in sweep if n != sweep[0]
    }
    base_streams = streams_by_n[sweep[0]]
    record["streams_identical_across_scales"] = all(
        streams_by_n[n] == base_streams for n in sweep
    )
    print(f"[scale] efficiency {record['scaling_efficiency']}, "
          f"streams identical: "
          f"{record['streams_identical_across_scales']}")

    with open(args.json, "w") as f:
        json.dump(record, f, indent=2, sort_keys=True)
    print(f"[scale] wrote {args.json}")
    return record


if __name__ == "__main__":
    main()
