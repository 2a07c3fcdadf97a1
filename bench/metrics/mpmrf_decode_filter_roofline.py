"""Roofline share of the fused decode filter kernel, in percent: the
least time its calls in the traced slice need at the chip's int8 and
HBM peaks (``bench/costs/mpmrf_decode_filter.py``: the bits the rounds
need, not the int16 codes the kernel streams) over the kernel's summed
device time."""

from bench.costs import mpmrf_decode_filter as cost

KERNEL = "mpmrf_decode_filter"


def read(run):
    if run.trace is None or not run.decode_calls:
        return None
    kernel_ns = run.trace.kernel_ns(KERNEL)
    if not kernel_ns:
        return None
    layers = run.cfg["num_hidden_layers"] - \
        run.cfg["energon"]["min_prune_layer"]
    least = 0.0
    for lengths, _ in run.decode_calls:
        ops, nbytes = cost.cost(run.cfg, lengths)
        least += layers * max(ops / run.peaks["int8_ops"],
                              nbytes / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (kernel_ns * 1e-9)
