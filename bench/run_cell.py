#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run_cell.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

One process, no children. It prints the platform, device kind and
count, and exits non-zero with no result unless JAX sees a TPU with as
many chips as the cell asks for. Set-up (weights from the seed, the
shared prefix, one warm-up request per slot) counts as ``setup_s``;
then the cell's traffic runs for ``--seconds``. With ``--trace 1`` a
slice of the window is traced and the per-layer metrics are reported
instead of the end-to-end ones. The last lines on standard error, and
the result line's ``compared`` key, give each number compared with the
reference beside its limit; the last line on standard output is the
result JSON.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness

    spec = harness.load_cell(args.workload)
    import jax

    devices = jax.devices()
    d0 = devices[0]
    print(f"[device] platform {d0.platform}, kind {d0.device_kind}, count "
          f"{len(devices)}", flush=True)
    if d0.platform != "tpu" or len(devices) < spec["chips"]:
        print(f"[device] the cell needs {spec['chips']} TPU chip(s); no "
              f"result", file=sys.stderr, flush=True)
        return 2
    harness.enable_compile_cache()
    result = harness.run(spec, args.seed, args.seconds, bool(args.trace),
                         T_PROCESS, log=lambda m: print(m, flush=True))
    for name, c in result["compared"].items():
        print(f"compared {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
