"""Model FLOP utilisation of the decode step, in percent: the FLOPs the
decoded tokens require (``bench/costs/decode_step.py``) over the decode
program's device time in the traced slice times the chip's bf16 peak."""

from bench.costs import decode_step
from bench.program import DECODE_MODULE


def read(run):
    if run.trace is None or not run.decode_calls:
        return None
    device_s = sum(run.trace.module_times(DECODE_MODULE)) * 1e-9
    if device_s <= 0:
        return None
    flops = sum(decode_step.flops(run.cfg, lengths[active])
                for lengths, active in run.decode_calls)
    return 100.0 * flops / (device_s * run.peaks["bf16_flops"])
