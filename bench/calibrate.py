#!/usr/bin/env python3
"""Readings that a cell's correctness limit is set from.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 \\
        --seconds 10 [--control]

For each seed, in one process on the chip: a run of the cell at its own
load for ``--seconds``, then the program's widest logit gap on the
finished sample and, with ``--control``, the float8 control's gap on the
same prompts and tokens. Prints one JSON line per seed and a summary
line. Benchmark runs never run the control; this script is how the
lower and upper readings in ``bench/limits/<cell>.json`` were taken.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)

    from bench import harness

    spec = harness.load_cell(args.workload)
    import jax

    if jax.devices()[0].platform != "tpu":
        print("calibration runs on the chip only", file=sys.stderr)
        return 2
    harness.enable_compile_cache()
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        out = harness.run(spec, seed, args.seconds, False, t0,
                          control=args.control, log=lambda m: None)
        c = out["compared"]
        row = {"seed": seed, "logit_gap_mean": c["logit_gap_mean"]["value"],
               "unfinished": c["unfinished"]["value"],
               "attempted": out["attempted"],
               "peak": out["device"]["memory_peak_bytes"],
               "seconds": time.perf_counter() - t0}
        if args.control:
            row["control_gap_mean"] = c["control_gap_mean"]["value"]
            for side in ("program", "control"):
                if out["gaps"][side] is None:
                    continue
                flat = sorted(g for req in out["gaps"][side] for g in req)
                row[side] = {
                    "tokens": len(flat),
                    "argmax_share": sum(g <= 0 for g in flat) / len(flat),
                    "mean": sum(flat) / len(flat),
                    "p50": flat[len(flat) // 2],
                    "p90": flat[int(0.9 * len(flat))],
                    "max": flat[-1],
                }
        rows.append(row)
        print(json.dumps(row), flush=True)
        gc.collect()
    summary = {"workload": args.workload,
               "max_logit_gap_mean": max(r["logit_gap_mean"] for r in rows)}
    if args.control:
        summary["min_control_gap_mean"] = min(r["control_gap_mean"]
                                             for r in rows)
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
